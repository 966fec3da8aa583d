"""Pallas TPU kernels: flash attention, paged decode attention, the routed
experts' grouped products, fused layer norm, fused softmax.

TPU-native replacement for the reference's hand-fused CUDA ops
(src/operator/contrib/transformer.cc fused attention projections,
nn/layer_norm.* CUDA kernels, softmax-inl.h) and the NVRTC pointwise fusion
engine (src/operator/fusion/fused_op.*). XLA already fuses elementwise chains;
these kernels cover what XLA won't fuse on its own — the attention
softmax(QK^T)V chain is materialization-bound at O(T^2) without an online-
softmax kernel.

Design:
- flash attention fwd is a Pallas kernel (online softmax, tiled over KV
  blocks, accumulation in fp32 VMEM scratch); backward is a blockwise
  recompute (two lax.scans over KV blocks, standard flash-bwd identities) so
  training memory stays O(T * block) — a hand-written Pallas bwd kernel is a
  possible further optimization.
- paged decode attention (``mxtpu_paged_decode``) serves the decode tick: one
  to a few queries a slot against the KV pages the slot's page-table row
  maps, read where they lie in the pool (page table, positions and layer id
  as scalar-prefetch operands; grid (slot, logical page); block = one page of
  one layer; online softmax in float32 on the vector unit). Forward only.
  Handed a K = 1 tick's new rows it also stores them (the page a slot's row
  falls in is the last it reads: merged there, copied back as one block).
- kernels engage only on the TPU backend with aligned shapes; everywhere else
  the mathematically identical XLA reference path runs, so the CPU test mesh
  exercises the same API.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

import os as _os

_NEG_INF = -1e30

# flash-attention tile-size floors (Mosaic minimum tiles: 8 sublanes on
# the Q axis, 128 lanes on the K axis)
_MIN_BLOCK_Q = 8
_MIN_BLOCK_K = 128


def _validated_block_env(name, default, min_tile) -> int:
    """Block size from env var ``name`` — read PER CALL, not at import,
    so tests and the tuner can vary it without reloading the module.
    Must be a power of two >= the Mosaic minimum tile for its axis."""
    from ..base import MXNetError

    raw = _os.environ.get(name, "")
    if not raw:
        return default
    try:
        v = int(raw)
    except ValueError:
        raise MXNetError(
            f"{name}={raw!r} is not an integer; expected a power of two "
            f">= {min_tile}") from None
    if v < min_tile or (v & (v - 1)) != 0:
        raise MXNetError(
            f"{name}={v} is invalid: flash-attention block sizes must be "
            f"powers of two >= {min_tile} (the Mosaic minimum tile for "
            "this axis)")
    return v


def flash_block_q() -> int:
    """Default Q-axis tile (``MXTPU_FLASH_BLOCK_Q``, default 256) — the
    starting point the tuner measures against, not a frozen constant."""
    return _validated_block_env("MXTPU_FLASH_BLOCK_Q", 256, _MIN_BLOCK_Q)


def flash_block_k() -> int:
    """Default K-axis tile (``MXTPU_FLASH_BLOCK_K``, default 512)."""
    return _validated_block_env("MXTPU_FLASH_BLOCK_K", 512, _MIN_BLOCK_K)


def _on_tpu() -> bool:
    from ..context import default_backend

    return default_backend() == "tpu"


def _interpret() -> bool:
    """Run Pallas kernels in interpreter mode (works on the CPU test mesh) —
    lets the kernel code paths be exercised without TPU hardware."""
    import os

    return os.environ.get("MXTPU_PALLAS_INTERPRET", "") == "1"


def _use_pallas() -> bool:
    return _HAVE_PALLAS and (_on_tpu() or _interpret())


# ---------------------------------------------------------------------------
# tuned-config resolution (trace-time only — block sizes are static args
# of the compiled programs, so steady state never pays a lookup)
# ---------------------------------------------------------------------------
def _tune_cache():
    from ..tune import cache

    return cache


def _resolve_attention_blocks(kernel, q, k, causal, seg):
    """(block_q, block_k) for this trace, or None for the XLA lowering.

    ``kernel`` is ``"flash_fwd"`` or ``"flash_bwd"`` — the two are tuned
    independently (their grids iterate opposite axes). With tuning off
    this returns the env-default blocks, byte-identical to the pre-tuner
    behavior; with tuning on, a miss or a tuned Pallas loss returns None
    so the caller takes the XLA path (never silently slower)."""
    tc = _tune_cache()
    cfg = tc.resolve(kernel, tc.key_attention(
        kernel, q.shape, k.shape, q.dtype, causal, seg))
    if cfg == "default":
        return flash_block_q(), flash_block_k()
    if cfg == "xla":
        return None
    return (int(cfg.get("block_q", flash_block_q())),
            int(cfg.get("block_k", flash_block_k())))


def _resolve_block_rows(kernel, rows, d, dtype):
    """block_rows for a row-wise kernel (``"layer_norm"``/``"softmax"``),
    or 0 for the XLA lowering."""
    tc = _tune_cache()
    cfg = tc.resolve(kernel, tc.key_rows(kernel, rows, d, dtype))
    if cfg == "default":
        return 128
    if cfg == "xla":
        return 0
    return int(cfg.get("block_rows", 128))


# ---------------------------------------------------------------------------
# reference (XLA) attention — also the vjp recompute path
# ---------------------------------------------------------------------------
def _attention_reference(q, k, v, scale, causal, q_seg=None, k_seg=None):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    masked = None
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        masked = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)[None, None]
    if q_seg is not None:
        seg = q_seg[:, None, :, None] == k_seg[:, None, None, :]
        masked = seg if masked is None else (masked & seg)
    if masked is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        s = jnp.where(masked, s, _NEG_INF)
        # where-masked softmax: a fully masked query row yields zeros, not
        # a uniform distribution (matters for padded batches)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(masked, jnp.exp(s - m), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# flash attention forward kernel
# ---------------------------------------------------------------------------
def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q,
                      block_k, seq_k, causal_offset=0, use_seg=False):
    if use_seg:
        qs_ref, ks_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    qb = pl.program_id(1)
    q = q_ref[0]  # (BQ, D) — stays in input dtype so the MXU runs bf16
    num_kb = seq_k // block_k

    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(kb, _):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        # bf16 (or f32) operands, fp32 accumulation on the MXU
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (BQ, BK) f32
        ok = None
        if causal:
            # bottom-right alignment (matches _attention_reference and the
            # custom_vjp backward): query i attends keys <= i + (Tk - Tq)
            qi = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            ki = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            ok = qi + causal_offset >= ki
        if use_seg:
            # tokens attend within their segment only (padding tokens get a
            # segment id of their own, so padded keys never contribute)
            ks = ks_ref[0, :, pl.ds(kb * block_k, block_k)]   # (1, BK)
            seg_ok = qs_ref[0] == ks                          # (BQ, BK)
            ok = seg_ok if ok is None else (ok & seg_ok)
        if ok is not None:
            s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if ok is not None:
            # zero p under the COMBINED mask: _NEG_INF is finite, so a row
            # with no visible keys in this block has s == m_new and p would
            # otherwise be 1 everywhere (fully masked rows must emit zeros,
            # matching the XLA reference and the bwd kernels)
            p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        return 0

    jax.lax.fori_loop(0, num_kb, body, 0)
    # fully masked rows (l == 0) output zeros, not NaN
    o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)
    # log-sum-exp per query row: saved for the backward kernels, which
    # reconstruct p = exp(s - lse) without a second online-softmax pass
    lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


try:  # pallas imports are deferred-safe: CPU-only installs still work
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _HAVE_PALLAS = True
except Exception:  # noqa: BLE001
    _HAVE_PALLAS = False


# Mosaic's scoped VMEM default on the chip; a kernel that keeps whole
# sequences resident asks for more only past this (v5e has 128 MiB)
_VMEM_SCOPED_DEFAULT = 16 * 2**20


def _vmem_params(resident_bytes):
    """``compiler_params`` for a kernel whose double-buffered resident
    operands take ``resident_bytes``: nothing while the chip's default scoped
    limit holds them (the programs of shorter sequences stay as they were),
    else a limit with room for the blocks and scratch beside them. At
    T 4096, D 256 in float32 one head's K and V are 4 MiB each, 16 MiB
    double-buffered, and the default refuses the kernel."""
    if resident_bytes + 4 * 2**20 <= _VMEM_SCOPED_DEFAULT:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(resident_bytes + 16 * 2**20, 100 * 2**20))}


def _flash_attention_tpu(q, k, v, scale, causal, block_q, block_k,
                         return_lse=False, q_seg=None, k_seg=None):
    """q,k,v: (B, H, T, D) with T % block == 0, D % 128 == 0 (pre-padded).
    q_seg/k_seg: optional (B, T) int32 segment ids."""
    if q_seg is not None:
        q_seg, k_seg = _seg_columns_rows(q_seg, k_seg)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    use_seg = q_seg is not None
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_k=tk,
        causal_offset=tk - tq, use_seg=use_seg)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qb: (bh, qb, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, tk, d), lambda bh, qb: (bh, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, tk, d), lambda bh, qb: (bh, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    operands = [qr, kr, vr]
    if use_seg:
        # segment ids are per-batch; grid dim 0 runs over b*h fused heads
        in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda bh, qb: (bh // h, qb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tk), lambda bh, qb: (bh // h, 0, 0),
                         memory_space=pltpu.VMEM),
        ]
        operands += [q_seg, k_seg]
    out, lse = pl.pallas_call(
        kernel,
        name="mxtpu_flash_fwd",
        grid=(b * h, tq // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qb: (bh, qb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda bh, qb: (bh, qb, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * tq * tk * d,
            bytes_accessed=(qr.size + kr.size + vr.size) * qr.dtype.itemsize,
            transcendentals=b * h * tq * tk,
        ),
        interpret=_interpret(),
        **_vmem_params(4 * tk * d * kr.dtype.itemsize),
    )(*operands)
    out = out.reshape(b, h, tq, d)
    if return_lse:
        return out, lse.reshape(b, h, tq, 1)
    return out


def _seg_columns_rows(q_seg, k_seg):
    """(B, T) segment ids in the layout the kernels read: query ids as a
    column (B, Tq, 1), key ids as a row (B, 1, Tk). Mosaic wants the last
    two dims of a block divisible by (8, 128) or equal to the array's, so
    a (1, block) slice of a (B, T) array is refused; here the size-1 dim
    equals the array's and the blocked dim sits where q/k/lse blocks
    already tile it. The kernels compare column == row with no relayout."""
    return (q_seg.astype(jnp.int32)[:, :, None],
            k_seg.astype(jnp.int32)[:, None, :])


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    rem = size % multiple
    if rem == 0:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, multiple - rem)
    return jnp.pad(x, pad), size


def flash_attention(q, k, v, scale=None, causal=False, q_segment_ids=None,
                    kv_segment_ids=None):
    """Fused attention over (B, H, T, D) operands.

    Pallas online-softmax kernel on TPU; identical XLA math elsewhere.
    ``q_segment_ids``/``kv_segment_ids`` are optional (B, T) int arrays:
    tokens attend only within matching segment ids, which
    covers BERT key-padding masks (valid tokens id 1, padding id 0) and
    packed sequences — without materializing an O(T²) mask.

    Ragged sequence lengths (not block-divisible, e.g. BERT T=384) stay on
    the fused path: operands are padded to block shape and the padding is
    hidden behind sentinel segment ids, then the output is sliced back.

    Block sizes resolve once per TRACE through the tuning tier
    (``tune.cache``): env defaults when tuning is off, the persisted
    per-bucket winner when on, block 0 (= the XLA lowering) on a miss or
    a tuned Pallas loss. They ride the custom_vjp as nondiff args so the
    backward sees the same forward decision.
    """
    if kv_segment_ids is None:
        kv_segment_ids = q_segment_ids
    if q_segment_ids is None:
        q_segment_ids = kv_segment_ids
    if _use_pallas():
        blocks = _resolve_attention_blocks("flash_fwd", q, k, causal,
                                           q_segment_ids is not None)
    else:
        # no Pallas here: blocks are inert (the reference path runs), so
        # skip the tuning tier — a CPU process logs no spurious misses
        blocks = (flash_block_q(), flash_block_k())
    if blocks is None:
        bq = bk = 0  # sentinel: XLA lowering
    else:
        bq, bk = blocks
        if _use_pallas():
            tq, tk = q.shape[2], k.shape[2]
            ok = _axis_tiles(tq, bq) and _axis_tiles(tk, bk)
            if not ok and (not causal or tq == tk):
                # under causal, padding both seqs by the SAME amount
                # preserves the bottom-right alignment offset (tk - tq);
                # with tq != tk that cannot be guaranteed, so those rare
                # shapes fall back
                return _flash_attention_padded(q, k, v, scale, causal,
                                               q_segment_ids,
                                               kv_segment_ids, bq, bk)
    if q_segment_ids is None:
        return _flash_attention_plain(q, k, v, scale, causal, bq, bk)
    return _flash_attention_seg(q, k, v,
                                q_segment_ids.astype(jnp.int32),
                                kv_segment_ids.astype(jnp.int32),
                                scale, causal, bq, bk)


def _block_padded_len(t, block):
    """Next multiple of ``block`` >= t. Reached only when some axis fails
    to tile; any t <= its own block size tiles trivially because the
    block clamps to min(block, t)."""
    return -(-t // block) * block


def _axis_tiles(t, block):
    return t % min(block, t) == 0


def _flash_attention_padded(q, k, v, scale, causal, q_seg, k_seg,
                            block_q, block_k):
    b, _, tq, d = q.shape
    tk = k.shape[2]
    if causal:  # tq == tk here: one common padded length keeps the offset
        lq = lk = max(_block_padded_len(tq, block_q),
                      _block_padded_len(tk, block_k))
    else:
        # pad only the axes that don't already tile (e.g. non-causal
        # T=384: q needs 512 but k tiles at bk=384 — leave k alone)
        lq = tq if _axis_tiles(tq, block_q) else \
            _block_padded_len(tq, block_q)
        lk = tk if _axis_tiles(tk, block_k) else \
            _block_padded_len(tk, block_k)

    def padt(x, length):
        return jnp.pad(x, ((0, 0), (0, 0), (0, length - x.shape[2]),
                           (0, 0)))

    if q_seg is None and (lk == tk or causal):
        # no masking needed: padded KEYS are either absent (k unpadded) or
        # causally invisible (common-length padding puts them at indices
        # >= tk > any real query's reach); padded QUERY rows are sliced
        # off and their zero output-cotangents keep the backward exact —
        # so the cheaper plain kernel runs, with no seg operands
        out = _flash_attention_plain(padt(q, lq), padt(k, lk),
                                     padt(v, lk), scale, causal,
                                     block_q, block_k)
        return out[:, :, :tq]
    if q_seg is None:
        q_seg = jnp.ones((b, tq), jnp.int32)
        k_seg = jnp.ones((b, tk), jnp.int32)
    # ids are doubled (even) so the ODD sentinels can never collide with
    # any user id — including negative ones; equality between real pairs
    # is preserved. (|id| must fit int32 after doubling.)
    q_seg = jnp.pad(q_seg.astype(jnp.int32) * 2, ((0, 0), (0, lq - tq)),
                    constant_values=-1)
    k_seg = jnp.pad(k_seg.astype(jnp.int32) * 2, ((0, 0), (0, lk - tk)),
                    constant_values=-3)
    out = _flash_attention_seg(padt(q, lq), padt(k, lk), padt(v, lk),
                               q_seg, k_seg, scale, causal,
                               block_q, block_k)
    return out[:, :, :tq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_plain(q, k, v, scale, causal, block_q, block_k):
    return _flash_attention_impl(q, k, v, scale, causal, block_q, block_k)


def _clamped_blocks(q, k, block_q, block_k):
    """Clamp raw (possibly bucket-sized) blocks to the actual seq axes and
    check tiling. block 0 is the XLA sentinel — never ok."""
    if block_q <= 0 or block_k <= 0:
        return 0, 0, False
    bq = min(block_q, q.shape[2])
    bk = min(block_k, k.shape[2])
    ok = q.shape[2] % bq == 0 and k.shape[2] % bk == 0
    return bq, bk, ok


def _flash_attention_impl(q, k, v, scale, causal, block_q, block_k):
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    if not _use_pallas():
        return _attention_reference(q, k, v, s, causal)
    # head_dim needs no padding (Mosaic handles sub-lane widths); the seq
    # axes must tile evenly by the block sizes
    bq, bk, ok = _clamped_blocks(q, k, block_q, block_k)
    if not ok:
        # XLA sentinel, or ragged shapes where padded KV rows would need
        # an extra mask: the reference path is simplest-correct
        return _attention_reference(q, k, v, s, causal)
    return _flash_attention_tpu(q, k, v, s, causal, bq, bk)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    bq, bk, ok = _clamped_blocks(q, k, block_q, block_k)
    if _use_pallas() and ok:
        out, lse = _flash_attention_tpu(q, k, v, s, causal, bq, bk,
                                        return_lse=True)
        return out, (q, k, v, out, lse)
    return _attention_reference(q, k, v, s, causal), (q, k, v, None, None)


# -- segment-ids (key padding / packed sequences) variant -------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_attention_seg(q, k, v, q_seg, k_seg, scale, causal,
                         block_q, block_k):
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    if not _use_pallas():
        return _attention_reference(q, k, v, s, causal, q_seg, k_seg)
    bq, bk, ok = _clamped_blocks(q, k, block_q, block_k)
    if not ok:
        return _attention_reference(q, k, v, s, causal, q_seg, k_seg)
    return _flash_attention_tpu(q, k, v, s, causal, bq, bk,
                                q_seg=q_seg, k_seg=k_seg)


def _flash_seg_fwd(q, k, v, q_seg, k_seg, scale, causal, block_q, block_k):
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    bq, bk, ok = _clamped_blocks(q, k, block_q, block_k)
    if _use_pallas() and ok:
        out, lse = _flash_attention_tpu(q, k, v, s, causal, bq, bk,
                                        return_lse=True,
                                        q_seg=q_seg, k_seg=k_seg)
        return out, (q, k, v, q_seg, k_seg, out, lse)
    out = _attention_reference(q, k, v, s, causal, q_seg, k_seg)
    return out, (q, k, v, q_seg, k_seg, None, None)


def _flash_seg_bwd(scale, causal, block_q, block_k, res, g):
    import numpy as onp

    q, k, v, q_seg, k_seg, out, lse = res
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    if lse is not None and _use_pallas():
        bwd = _resolve_attention_blocks("flash_bwd", q, k, causal, True)
        if bwd is not None:
            bq, bk, ok = _clamped_blocks(q, k, *bwd)
            if ok:
                dq, dk, dv = _flash_bwd_tpu(q, k, v, out, lse, g, s,
                                            causal, bq, bk,
                                            q_seg=q_seg, k_seg=k_seg)
                return (dq, dk, dv,
                        onp.zeros(q_seg.shape, jax.dtypes.float0),
                        onp.zeros(k_seg.shape, jax.dtypes.float0))
    dq, dk, dv = _attention_bwd_blockwise(q, k, v, g, s, causal,
                                          q_seg=q_seg, k_seg=k_seg)
    return (dq, dk, dv,
            onp.zeros(q_seg.shape, jax.dtypes.float0),
            onp.zeros(k_seg.shape, jax.dtypes.float0))


_flash_attention_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


# ---------------------------------------------------------------------------
# flash attention backward kernels
#
# Standard flash-bwd identities with the forward's saved LSE:
#   p_ij  = exp(s_ij - lse_i)
#   dv_j  = Σ_i p_ij g_i
#   dp_ij = g_i · v_j
#   ds_ij = p_ij (dp_ij - Δ_i) * scale,   Δ_i = Σ_d g_id o_id
#   dq_i  = Σ_j ds_ij k_j ;  dk_j = Σ_i ds_ij q_i
# Two kernels: one gridded over KV blocks (dk, dv), one over Q blocks (dq).
# No O(T²) materialization; accumulation in fp32 VMEM scratch.
# ---------------------------------------------------------------------------
def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          *rest, scale, causal, block_q, block_k, seq_q,
                          causal_offset, use_seg=False):
    if use_seg:
        qs_ref, ks_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    kb = pl.program_id(1)
    k = k_ref[0]  # (BK, D)
    v = v_ref[0]
    num_qb = seq_q // block_q

    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    def body(qb, _):
        q = q_ref[0, pl.ds(qb * block_q, block_q), :]
        g = g_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]   # (BQ, 1)
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (BQ, BK)
        ok = None
        if causal:
            qi = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            ki = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            ok = qi + causal_offset >= ki
            s = jnp.where(ok, s, _NEG_INF)
        p = jnp.exp(s - lse)                                  # normalized
        if use_seg:
            qs = qs_ref[0, pl.ds(qb * block_q, block_q), :]   # (BQ, 1)
            seg_ok = qs == ks_ref[0]                          # (BQ, BK)
            ok = seg_ok if ok is None else (ok & seg_ok)
        if ok is not None:
            # mask p under the COMBINED mask: for a fully masked row lse
            # was clamped, so exp(s - lse) is not reliably ~0 there
            p = jnp.where(ok, p, 0.0)
        gf = g.astype(jnp.float32)
        dv_acc[:] += jax.lax.dot_general(
            p, gf, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (BK, D)
        dp = jax.lax.dot_general(
            gf, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (BQ, BK)
        ds = p * (dp - delta) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (BK, D)
        return 0

    jax.lax.fori_loop(0, num_qb, body, 0)
    dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         *rest, scale, causal, block_q,
                         block_k, seq_k, causal_offset, use_seg=False):
    if use_seg:
        qs_ref, ks_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
    qb = pl.program_id(1)
    q = q_ref[0]   # (BQ, D)
    g = g_ref[0]
    lse = lse_ref[0]    # (BQ, 1)
    delta = delta_ref[0]
    num_kb = seq_k // block_k

    dq_acc[:] = jnp.zeros_like(dq_acc)
    gf = g.astype(jnp.float32)

    def body(kb, _):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        ok = None
        if causal:
            qi = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            ki = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            ok = qi + causal_offset >= ki
            s = jnp.where(ok, s, _NEG_INF)
        p = jnp.exp(s - lse)
        if use_seg:
            ks = ks_ref[0, :, pl.ds(kb * block_k, block_k)]   # (1, BK)
            seg_ok = qs_ref[0] == ks                          # (BQ, BK)
            ok = seg_ok if ok is None else (ok & seg_ok)
        if ok is not None:
            p = jnp.where(ok, p, 0.0)
        dp = jax.lax.dot_general(
            gf, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return 0

    jax.lax.fori_loop(0, num_kb, body, 0)
    dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_tpu(q, k, v, out, lse, g, scale, causal, block_q, block_k,
                   q_seg=None, k_seg=None):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    qr = q.reshape(b * h, tq, d)
    kr = k.reshape(b * h, tk, d)
    vr = v.reshape(b * h, tk, d)
    gr = g.reshape(b * h, tq, d)
    lser = lse.reshape(b * h, tq, 1)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True).reshape(b * h, tq, 1)
    off = tk - tq
    use_seg = q_seg is not None
    if use_seg:
        q_seg, k_seg = _seg_columns_rows(q_seg, k_seg)

    full_q = pl.BlockSpec((1, tq, d), lambda bh, blk: (bh, 0, 0),
                          memory_space=pltpu.VMEM)
    full_k = pl.BlockSpec((1, tk, d), lambda bh, blk: (bh, 0, 0),
                          memory_space=pltpu.VMEM)
    full_stat = pl.BlockSpec((1, tq, 1), lambda bh, blk: (bh, 0, 0),
                             memory_space=pltpu.VMEM)
    kv_blk = pl.BlockSpec((1, block_k, d), lambda bh, kb: (bh, kb, 0),
                          memory_space=pltpu.VMEM)
    dkv_in_specs = [full_q, kv_blk, kv_blk, full_q, full_stat, full_stat]
    dkv_operands = [qr, kr, vr, gr, lser, delta]
    if use_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, tq, 1), lambda bh, kb: (bh // h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k), lambda bh, kb: (bh // h, 0, kb),
                         memory_space=pltpu.VMEM),
        ]
        dkv_operands += [q_seg, k_seg]
    dkv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_q=tq,
                          causal_offset=off, use_seg=use_seg),
        name="mxtpu_flash_bwd_dkv",
        grid=(b * h, tk // block_k),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, kb: (bh, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda bh, kb: (bh, kb, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=5 * b * h * tq * tk * d,
            bytes_accessed=(qr.size * 2 + kr.size * 3) * qr.dtype.itemsize,
            transcendentals=b * h * tq * tk,
        ),
        interpret=_interpret(),
        **_vmem_params(4 * tq * d * qr.dtype.itemsize),
    )(*dkv_operands)
    dk, dv = dkv

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qb: (bh, qb, 0),
                     memory_space=pltpu.VMEM),
        full_k, full_k,
        pl.BlockSpec((1, block_q, d), lambda bh, qb: (bh, qb, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), lambda bh, qb: (bh, qb, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), lambda bh, qb: (bh, qb, 0),
                     memory_space=pltpu.VMEM),
    ]
    dq_operands = [qr, kr, vr, gr, lser, delta]
    if use_seg:
        dq_in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda bh, qb: (bh // h, qb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tk), lambda bh, qb: (bh // h, 0, 0),
                         memory_space=pltpu.VMEM),
        ]
        dq_operands += [q_seg, k_seg]
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=tk,
                          causal_offset=off, use_seg=use_seg),
        name="mxtpu_flash_bwd_dq",
        grid=(b * h, tq // block_q),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qb: (bh, qb, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=3 * b * h * tq * tk * d,
            bytes_accessed=(qr.size * 2 + kr.size * 2) * qr.dtype.itemsize,
            transcendentals=b * h * tq * tk,
        ),
        interpret=_interpret(),
        **_vmem_params(4 * tk * d * kr.dtype.itemsize),
    )(*dq_operands)
    return (dq.reshape(b, h, tq, d), dk.reshape(b, h, tk, d),
            dv.reshape(b, h, tk, d))


_BWD_BLOCK = 512


def _attention_bwd_blockwise(q, k, v, g, scale, causal, q_seg=None,
                             k_seg=None):
    """Memory-capped attention backward: recompute scores blockwise over KV.

    Standard flash-attention backward structure without a hand-written
    kernel: two passes of lax.scan over KV blocks keep peak memory at
    O(T * block) instead of O(T^2), so long-context training fits in HBM.
    XLA fuses each block's matmul chain onto the MXU.
    """
    b, h, tq, d = q.shape
    tk = k.shape[2]
    # largest divisor of tk up to the cap keeps the memory bound for ANY
    # block-unfriendly length; only tiny/pathological divisors (where the
    # scan would degenerate) fall back to the dense vjp — and those lengths
    # are small enough that O(T^2) is not a memory problem
    blk = max((d_ for d_ in range(1, min(_BWD_BLOCK, tk) + 1)
               if tk % d_ == 0), default=tk)
    if blk < 16 and tk > 4096:
        blk = 1  # prime-ish huge tk: still capped, just slower
    elif blk < 16:
        _, vjp = jax.vjp(lambda q_, k_, v_:
                         _attention_reference(q_, k_, v_, scale, causal,
                                              q_seg, k_seg),
                         q, k, v)
        return vjp(g)
    nblk = tk // blk
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    kb = k.reshape(b, h, nblk, blk, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nblk, blk, d).transpose(2, 0, 1, 3, 4)

    def mask_for(idx):
        m = None
        if causal:
            qi = jnp.arange(tq)[:, None] + (tk - tq)
            ki = idx * blk + jnp.arange(blk)[None, :]
            m = (qi >= ki)[None, None]
        if q_seg is not None:
            ks_i = lax.dynamic_slice_in_dim(k_seg, idx * blk, blk, axis=1)
            seg = q_seg[:, None, :, None] == ks_i[:, None, None, :]
            m = seg if m is None else (m & seg)
        return m

    # pass 1: softmax stats (row max m, denominator l) + output recompute
    def stats_step(carry, inputs):
        m_prev, l_prev, acc = carry
        kb_i, vb_i, idx = inputs
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb_i.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        msk = mask_for(idx)
        if msk is not None:
            s = jnp.where(msk, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if msk is not None:
            p = jnp.where(msk, p, 0.0)  # fully masked rows: l stays 0
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb_i.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, h, tq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tq, 1), jnp.float32)
    a0 = jnp.zeros((b, h, tq, d), jnp.float32)
    (m, l, acc), _ = lax.scan(stats_step, (m0, l0, a0),
                              (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l, 1e-30)
    # delta_i = sum_d g_i * o_i (standard flash bwd identity)
    delta = jnp.sum(gf * out, axis=-1, keepdims=True)

    # pass 2: gradients per KV block
    def grad_step(dq_acc, inputs):
        kb_i, vb_i, idx = inputs
        kf = kb_i.astype(jnp.float32)
        vf = vb_i.astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf,
                       preferred_element_type=jnp.float32) * scale
        msk = mask_for(idx)
        if msk is not None:
            s = jnp.where(msk, s, _NEG_INF)
        p = jnp.exp(s - m) / jnp.maximum(l, 1e-30)  # (b,h,q,blk)
        if msk is not None:
            p = jnp.where(msk, p, 0.0)
        dv_i = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf)
        ds = p * (dp - delta) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
        dk_i = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq_acc, (dk_i, dv_i)

    dq, (dk_b, dv_b) = lax.scan(grad_step, jnp.zeros_like(qf),
                                (kb, vb, jnp.arange(nblk)))
    dk = dk_b.transpose(1, 2, 0, 3, 4).reshape(b, h, tk, d)
    dv = dv_b.transpose(1, 2, 0, 3, 4).reshape(b, h, tk, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_bwd(scale, causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    if lse is not None and _use_pallas():
        # the backward resolves its own tuned config: its grids iterate
        # the opposite axes from the forward, so the winners differ
        bwd = _resolve_attention_blocks("flash_bwd", q, k, causal, False)
        if bwd is not None:
            bq, bk, ok = _clamped_blocks(q, k, *bwd)
            if ok:
                return _flash_bwd_tpu(q, k, v, out, lse, g, s, causal,
                                      bq, bk)
    return _attention_bwd_blockwise(q, k, v, g, s, causal)


_flash_attention_plain.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# fused layer norm
# ---------------------------------------------------------------------------
def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    o_ref[:] = (xc * inv * g_ref[:].astype(jnp.float32) +
                b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rows_of(shape):
    rows = 1
    for sdim in shape[:-1]:
        rows *= sdim
    return rows


def fused_layer_norm(x, gamma, beta, eps=1e-5, block_rows=None):
    """Row-wise LayerNorm over the last axis (Pallas on TPU, XLA elsewhere).

    Differentiable: forward runs the kernel, backward flows through the
    identical XLA formula via jax.custom_vjp below.

    ``block_rows=None`` resolves through the tuning tier per trace
    (env default 128 when tuning is off; 0 = the XLA lowering on a miss
    or a tuned Pallas loss); pass an explicit value to pin it.
    """
    if block_rows is None:
        if _use_pallas() and x.shape[-1] % 128 == 0:
            block_rows = _resolve_block_rows("layer_norm",
                                             _rows_of(x.shape),
                                             x.shape[-1], x.dtype)
        else:
            # kernel can't run here anyway — don't log a tuning miss
            block_rows = 128
    return _fused_ln(x, gamma, beta, eps, int(block_rows))


def _ln_reference(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    inv = lax.rsqrt(var.astype(jnp.float32) + eps).astype(x.dtype)
    # keep x's dtype even with f32 gamma/beta so the Pallas-kernel primal
    # and this reference (used for the VJP) agree on output type
    return ((x - mean) * inv * gamma + beta).astype(x.dtype)


def _pad_rows(xr, br):
    """Pad the row axis up to a multiple of ``br`` (zero rows — sliced
    off after the kernel, so their values never escape)."""
    rows = xr.shape[0]
    target = -(-rows // br) * br
    if target == rows:
        return xr, rows
    return jnp.pad(xr, ((0, target - rows), (0, 0))), rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ln(x, gamma, beta, eps, block_rows):
    if not _use_pallas() or block_rows <= 0:
        return _ln_reference(x, gamma, beta, eps)
    d = x.shape[-1]
    if d % 128 != 0:
        # the feature axis cannot be padded (it changes the row mean);
        # non-lane-aligned widths stay on the reference path
        return _ln_reference(x, gamma, beta, eps)
    orig_shape = x.shape
    rows = _rows_of(orig_shape)
    br = min(block_rows, rows)
    # ragged row counts stay fused: pad tail rows, slice them back off
    xr, rows = _pad_rows(x.reshape(rows, d), br)
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        name="mxtpu_layer_norm",
        grid=(xr.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((d,), lambda i: (0,), memory_space=pltpu.VMEM),
            pl.BlockSpec((d,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((xr.shape[0], d), x.dtype),
        interpret=_interpret(),
    )(xr, gamma, beta)
    return out[:rows].reshape(orig_shape)


def _fused_ln_fwd(x, gamma, beta, eps, block_rows):
    return _fused_ln(x, gamma, beta, eps, block_rows), (x, gamma, beta)


def _fused_ln_bwd(eps, block_rows, res, g):
    x, gamma, beta = res
    _, vjp = jax.vjp(lambda x_, g_, b_: _ln_reference(x_, g_, b_, eps),
                     x, gamma, beta)
    return vjp(g)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


# ---------------------------------------------------------------------------
# fused softmax (last axis)
# ---------------------------------------------------------------------------
def _softmax_kernel(x_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[:] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


def fused_softmax(x, block_rows=None):
    """Last-axis softmax (Pallas on TPU, XLA elsewhere) — same gate audit
    as attention/LayerNorm: ``_use_pallas()`` + lane-aligned width, with
    ragged row counts padded to the block and sliced back. ``block_rows``
    resolves through the tuning tier when None.
    """
    if block_rows is None:
        if _use_pallas() and x.shape[-1] % 128 == 0:
            block_rows = _resolve_block_rows("softmax", _rows_of(x.shape),
                                             x.shape[-1], x.dtype)
        else:
            block_rows = 128
    return _fused_softmax(x, int(block_rows))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fused_softmax(x, block_rows):
    return _fused_softmax_impl(x, block_rows)


def _fused_softmax_impl(x, block_rows):
    d = x.shape[-1]
    if not _use_pallas() or block_rows <= 0 or d % 128 != 0:
        return jax.nn.softmax(x, axis=-1)
    rows = _rows_of(x.shape)
    br = min(block_rows, rows)
    xr, rows = _pad_rows(x.reshape(rows, d), br)
    out = pl.pallas_call(
        _softmax_kernel,
        name="mxtpu_softmax",
        grid=(xr.shape[0] // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((xr.shape[0], d), x.dtype),
        interpret=_interpret(),
    )(xr)
    return out[:rows].reshape(x.shape)


def _fused_softmax_fwd(x, block_rows):
    y = _fused_softmax_impl(x, block_rows)
    return y, y


def _fused_softmax_bwd(block_rows, y, g):
    gy = (g - jnp.sum(g * y, axis=-1, keepdims=True)) * y
    return (gy,)


_fused_softmax.defvjp(_fused_softmax_fwd, _fused_softmax_bwd)


# ---------------------------------------------------------------------------
# paged decode attention: a slot's K newest queries against the pages its
# table maps, read where they lie in the pool
# ---------------------------------------------------------------------------
def paged_decode_attention(q, k_pool, v_pool, layer, page_table, positions,
                           scale=None, k=None, v=None):
    """Decode attention straight from a paged KV pool.

    q : (S, K, Hq, D) — query k of slot s stands at ``positions[s] + k``.
    k_pool / v_pool : [num_pages, layers, Hkv, D, page_tokens] — a page of
        one layer holds its positions along the LAST axis (the lanes), so a
        page is one contiguous block and scores want no re-lay. ``Hq`` is a
        multiple of ``Hkv``: query heads ``g*i .. g*i + g - 1`` read KV head
        ``i`` (grouped-query attention; the page is read once a group).
    layer : int32 scalar (an operand, not an attribute: one program serves
        every layer). page_table : (S, W+1) int32, logical page -> pool page,
        ``num_pages`` marking an unmapped column. positions : (S,) int32.
    k / v : (S, 1, Hkv, D) in the pools' dtype, or None — the slots' NEW
        rows (K = 1 only: K rows can straddle two pages). Slot s's row goes
        into cell ``positions[s] % page_tokens`` of the page its table maps
        for ``positions[s]`` before the query attends, so the query sees its
        own position; a slot whose page there is unmapped, or whose position
        lies past the table, writes nothing. Every other byte of the pools
        stays as it is.

    Query k attends the positions ``<= positions[s] + k`` that lie in mapped
    pages: nothing past a slot's length, and nothing of an unmapped page, is
    read into the result (such cells may hold anything, NaN included). A
    slot with no mapped page — an inactive one — returns ZEROS.
    Returns (S, K, Hq, D) in q's dtype, scores and sums in float32; given
    rows, ``(out, k_pool, v_pool)`` with the pools as written.

    Pallas kernel on TPU (block = one page, no tuning knob; given rows it
    merges each into the slot's last page, which it reads anyway, and writes
    that one page back into the aliased pool); the page-wise write and the
    gather + mask + softmax of the same numbers elsewhere."""
    d, p = k_pool.shape[-2:]
    s = float(scale) if scale is not None else 1.0 / d ** 0.5
    layer = jnp.asarray(layer, jnp.int32)
    page_table = page_table.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    if k is not None and q.shape[1] != 1:
        from ..base import MXNetError

        raise MXNetError(
            f"paged_decode_attention stores the rows of a K = 1 tick only "
            f"(got K = {q.shape[1]}): write the rows first")
    if _use_pallas() and p % 128 == 0 and d % 8 == 0:
        return _paged_decode_tpu(q, k_pool, v_pool, layer, page_table,
                                 positions, s, k, v)
    if k is None:
        return _paged_decode_reference(q, k_pool, v_pool, layer, page_table,
                                       positions, s)
    k_pool = _write_row_reference(k_pool, layer, page_table, positions, k)
    v_pool = _write_row_reference(v_pool, layer, page_table, positions, v)
    return _paged_decode_reference(q, k_pool, v_pool, layer, page_table,
                                   positions, s), k_pool, v_pool


def _write_row_reference(pool, layer, page_table, positions, rows):
    """The plain write of a K = 1 tick: read the page each slot's position
    falls in, set the one cell, write the page back; the sentinel id (an
    unmapped column, or column W for a position past the table) reads a
    clamped page and its update is dropped."""
    p = pool.shape[-1]
    w = page_table.shape[1] - 1
    ids = jnp.take_along_axis(
        page_table, jnp.minimum(positions // p, w)[:, None], axis=1)[:, 0]
    hit = jnp.arange(p, dtype=jnp.int32) == (positions % p)[:, None]  # (S,P)
    new = jnp.where(hit[:, None, None, :], rows[:, 0, :, :, None],
                    pool[ids, layer])                  # (S, Hkv, D, P)
    return pool.at[ids, layer].set(new, mode="drop")


def _paged_decode_reference(q, k_pool, v_pool, layer, page_table, positions,
                            scale):
    """The plain body: gather every column of every slot's table row into a
    (S, Hkv, W*P, D) view, mask, softmax, weigh."""
    num_pages, _, hkv, d, p = k_pool.shape
    s, kq, hq, _ = q.shape
    w = page_table.shape[1] - 1
    ids = page_table[:, :w]
    kpos = jnp.arange(w * p, dtype=jnp.int32)
    qpos = positions[:, None] + jnp.arange(kq, dtype=jnp.int32)   # (S, K)
    ok = (kpos[None, None, :] <= qpos[:, :, None]) \
        & jnp.repeat(ids < num_pages, p, axis=1)[:, None, :]      # (S,K,WP)

    def view(pool):
        pages = pool[ids.reshape(-1), layer]    # (S*W, Hkv, D, P); clamps
        pages = pages.reshape(s, w, hkv, d, p).transpose(0, 2, 1, 4, 3)
        pages = pages.reshape(s, hkv, w * p, d)
        # the last query sees the most: what none may see reads as zero
        pages = jnp.where(ok[:, -1][:, None, :, None], pages, 0)
        return jnp.repeat(pages, hq // hkv, axis=1) if hq != hkv else pages

    kh, vh = view(k_pool), view(v_pool)
    qh = q.transpose(0, 2, 1, 3)                                  # (S,Hq,K,D)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32) * scale
    mask = ok[:, None]
    logits = jnp.where(mask, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(logits - m), 0.0)
    probs = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(vh.dtype), vh)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _paged_decode_kernel(tab_ref, pos_ref, lay_ref, q_ref, k_ref, v_ref,
                         *refs, num_pages, group, write):
    """Grid (slot, logical page). ``q_ref``/``o_ref``: (K, D, Hq) of the
    slot, heads along the lanes; ``k_ref``/``v_ref``: (Hkv, D, P), one page
    of one layer. Everything runs on the vector unit in float32: one query
    a head is a matrix-vector product, which the matrix unit would spend on
    loading each (D, P) page as weights. Scratch, per query k and head h:
    ``qb_ref`` the scaled query broadcast along the lanes (D, P),
    ``acc_ref`` the running sum of probs x V, still spread over the lanes
    (reduced once, at the slot's last step), ``m_ref``/``l_ref`` (Hq, P) the
    running maximum and sum, the same in every lane. The heads are unrolled
    and the softmax bookkeeping runs on all of them at once: a loop over
    heads with a (1, P) row each took 2.4 times as long on the chip, and
    its unrolled form 1.2 times.

    ``write`` (K = 1): ``kn_ref``/``vn_ref`` (D, Hkv) hold the slot's new
    row, heads along the lanes; ``ko_ref``/``vo_ref`` are the pools again,
    whole, where they lie (aliased to the inputs). At the step of the page
    the slot's position falls in, the row goes into its lane of the page
    just read and the merged page is copied back over it from a scratch
    (``kw_ref``/``vw_ref``), one whole aligned block a pool; the copies are
    waited for at the slot's last step, before the next slot merges. A slot
    with no page there starts no copy."""
    if write:
        kn_ref, vn_ref, o_ref, ko_ref, vo_ref, *refs = refs
        qb_ref, acc_ref, m_ref, l_ref, s_ref, kw_ref, vw_ref, sem = refs
    else:
        o_ref, qb_ref, acc_ref, m_ref, l_ref, s_ref = refs
    si, j = pl.program_id(0), pl.program_id(1)
    kq, d, hq = q_ref.shape
    p = k_ref.shape[-1]
    pos = pos_ref[si]
    head = jax.lax.broadcasted_iota(jnp.int32, (d, hq), 1)

    @pl.when(j == 0)
    def _start():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for k in range(kq):
            qk = q_ref[k].astype(jnp.float32)                    # (D, Hq)
            for h in range(hq):
                col = jnp.sum(jnp.where(head == h, qk, 0.0), axis=1,
                              keepdims=True)                     # (D, 1)
                qb_ref[k, h] = jnp.broadcast_to(col, (d, p))

    kpos = j * p + jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)

    if write:
        w = pl.num_programs(1)
        at = pos // p                 # the column the row's page stands in
        page = tab_ref[si, jnp.minimum(at, w - 1)]
        writes = (at < w) & (page < num_pages)
        copies = [pltpu.make_async_copy(src, dst.at[page, lay_ref[0]],
                                        sem.at[i])
                  for i, (src, dst) in enumerate([(kw_ref, ko_ref),
                                                  (vw_ref, vo_ref)])]
        kv_head = jax.lax.broadcasted_iota(jnp.int32, kn_ref.shape, 1)

        @pl.when(writes & (j == at))
        def _merge():
            # the merged page twice: over the block just read, where the
            # ONE page body below reads it (a second body for the scratch
            # cost seconds of tracing a program; a block is refetched only
            # when its index changes, and no later step of this slot reads
            # it), and in the scratch the copies leave from (theirs until
            # the wait)
            for new_ref, page_ref, merged_ref in [(kn_ref, k_ref, kw_ref),
                                                  (vn_ref, v_ref, vw_ref)]:
                new = new_ref[...].astype(jnp.float32)           # (D, Hkv)
                for h in range(new.shape[1]):
                    col = jnp.sum(jnp.where(kv_head == h, new, 0.0), axis=1,
                                  keepdims=True)                 # (D, 1)
                    merged = jnp.where(
                        kpos == pos, col, page_ref[h].astype(jnp.float32)
                    ).astype(merged_ref.dtype)
                    page_ref[h] = merged
                    merged_ref[h] = merged
            for copy in copies:
                copy.start()

        @pl.when(writes & (j == w - 1))
        def _written():
            for copy in copies:
                copy.wait()

    @pl.when((j * p <= pos + kq - 1) & (tab_ref[si, j] < num_pages))
    def _page():
        for k in range(kq):
            ok = kpos <= pos + k                                 # (1, P)
            for h in range(hq):
                s_ref[h:h + 1, :] = jnp.sum(
                    k_ref[h // group].astype(jnp.float32) * qb_ref[k, h],
                    axis=0, keepdims=True)
            s = jnp.where(ok, s_ref[...], _NEG_INF)              # (Hq, P)
            m_prev = m_ref[k]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # _NEG_INF is finite: a query that sees nothing of this page
            # has s == m_new there, and only the mask makes its p zero
            pr = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[k] = l_ref[k] * alpha + jnp.sum(pr, axis=-1, keepdims=True)
            m_ref[k] = m_new
            for h in range(hq):
                # V past the slot's length may hold anything: 0 x NaN
                v = jnp.where(ok, v_ref[h // group].astype(jnp.float32), 0.0)
                acc_ref[k, h] = acc_ref[k, h] * alpha[h:h + 1, :] \
                    + pr[h:h + 1, :] * v

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        for k in range(kq):
            inv = 1.0 / jnp.maximum(l_ref[k], 1e-30)             # (Hq, P)
            out = jnp.zeros((d, hq), jnp.float32)
            for h in range(hq):
                col = jnp.sum(acc_ref[k, h] * inv[h:h + 1, :], axis=1,
                              keepdims=True)                     # (D, 1)
                out = jnp.where(head == h, col, out)
            o_ref[k] = out.astype(o_ref.dtype)


def _paged_decode_tpu(q, k_pool, v_pool, layer, page_table, positions, scale,
                      k_new=None, v_new=None):
    num_pages, _, hkv, d, p = k_pool.shape
    s, kq, hq, _ = q.shape
    w = page_table.shape[1] - 1
    write = k_new is not None

    def page(si, j, tab, pos, lay):
        # past the slot's last live page the block index repeats, and a
        # repeated index moves no bytes; the sentinel id (one past the
        # pool) clamps into it
        last = jnp.clip((pos[si] + kq - 1) // p, 0, w - 1)
        return (jnp.minimum(tab[si, jnp.minimum(j, last)], num_pages - 1),
                lay[0], 0, 0, 0)

    def slot(si, j, tab, pos, lay):
        return (si, 0, 0, 0)

    pool_spec = pl.BlockSpec((None, None, hkv, d, p), page)
    q_spec = pl.BlockSpec((None, kq, d, hq), slot)
    itemsize = k_pool.dtype.itemsize
    scratch = 4 * (2 * kq * hq * d * p + (2 * kq + 1) * hq * p)
    operands = [(q * scale).transpose(0, 1, 3, 2), k_pool, v_pool]
    in_specs = [q_spec, pool_spec, pool_spec]
    out_specs = q_spec
    out_shape = jax.ShapeDtypeStruct((s, kq, d, hq), q.dtype)
    scratch_shapes = [
        pltpu.VMEM((kq, hq, d, p), jnp.float32),     # qb
        pltpu.VMEM((kq, hq, d, p), jnp.float32),     # acc
        pltpu.VMEM((kq, hq, p), jnp.float32),        # m
        pltpu.VMEM((kq, hq, p), jnp.float32),        # l
        pltpu.VMEM((hq, p), jnp.float32),            # scores
    ]
    aliases = {}
    if write:
        # the new rows as the queries are laid, heads along the lanes; the
        # pools come back whole and where they lie: operand 4 (after the
        # three prefetched scalars) is output 1, operand 5 output 2
        row_spec = pl.BlockSpec((None, None, d, hkv), slot)
        operands += [x.astype(k_pool.dtype).transpose(0, 1, 3, 2)
                     for x in (k_new, v_new)]
        in_specs += [row_spec, row_spec]
        whole = pl.BlockSpec(memory_space=pl.ANY)
        out_specs = [out_specs, whole, whole]
        out_shape = [out_shape] + [
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (k_pool, v_pool)]
        scratch_shapes += [pltpu.VMEM((hkv, d, p), k_pool.dtype),   # merged
                           pltpu.VMEM((hkv, d, p), v_pool.dtype),
                           pltpu.SemaphoreType.DMA((2,))]
        aliases = {4: 1, 5: 2}
    pages = 2 * hkv * d * p * itemsize               # a K and a V page
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, num_pages=num_pages,
                          group=hq // hkv, write=write),
        name="mxtpu_paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s, w),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        input_output_aliases=aliases,
        cost_estimate=pl.CostEstimate(
            flops=4 * s * kq * hq * d * w * p,
            bytes_accessed=s * (w + write) * pages,
            transcendentals=2 * s * kq * hq * w * p),
        interpret=_interpret(),
        **_vmem_params(scratch + (2 + write) * pages),
    )(page_table, positions, layer.reshape(1), *operands)
    if write:
        return (out[0].transpose(0, 1, 3, 2),) + tuple(out[1:])
    return out.transpose(0, 1, 3, 2)


# ---------------------------------------------------------------------------
# routed experts, forward: the two grouped products of a routed layer over
# the tiled layout of ``ops/moe.py::_plan``, each expert's matrices read
# where they lie in the stacked arrays, each token's weighted sum kept in
# fast memory
# ---------------------------------------------------------------------------
def _experts_resident(n, tm, d, f, x_dtype, w_dtype):
    """Bytes the kernel keeps in fast memory: an expert's two matrices and a
    tile of rows, each double-buffered, the tokens' sums (the result's
    block, two buffers), a tile's results and the float32 products."""
    w = jnp.dtype(w_dtype).itemsize
    return (2 * 3 * d * f * w + 2 * tm * d * jnp.dtype(x_dtype).itemsize
            + 2 * n * d * 4 + tm * d * 4 + 4 * tm * (3 * f + d))


def experts_kernel_serves(n, tm, d, f, x_dtype, w_dtype):
    """Whether ``grouped_experts`` takes these shapes: the lanes whole, the
    tile whole sublanes of the rows' type, and an expert resident twice
    (the one in use, the next one on its way) beside the tokens' sums
    inside what ``_vmem_params`` may ask for. Anything else is the loop's."""
    sublanes = 32 // jnp.dtype(x_dtype).itemsize
    return (_use_pallas() and d % 128 == 0 and f % 128 == 0
            and tm % sublanes == 0
            and _experts_resident(n, tm, d, f, x_dtype, w_dtype)
            <= 84 * 2**20)


def _experts_kernel(te_ref, nt_ref, rows_ref, tok_ref, w_ref, x_ref, gu_ref,
                    dn_ref, y_ref, o_ref):
    """Grid (tile,). ``x_ref`` (tm, D): the rows of one tile, all of one
    expert; ``gu_ref`` (D, 2F) and ``dn_ref`` (F, D): that expert's
    matrices, whole; ``y_ref`` (N, D) float32: every token's sum, resident
    from the first tile to the last; ``o_ref`` (tm, D) float32 scratch: the
    tile's results. Both products accumulate in float32 on the matrix unit,
    which takes bfloat16: float32 rows and weights are rounded to it here,
    as the chip's default precision rounds them, and SwiGLU runs on the
    float32 product and is rounded once. A tile with at most 32 rows in use
    multiplies its first 32 only. Then each row in use is weighted and added
    to its token's sum, in float32."""
    del te_ref, nt_ref                # the index maps read them
    t = pl.program_id(0)
    tm = x_ref.shape[0]
    f = dn_ref.shape[0]
    rows = rows_ref[t]

    @pl.when(t == 0)
    def _start():
        y_ref[...] = jnp.zeros_like(y_ref)

    def products(m):
        h = jnp.dot(x_ref[:m].astype(jnp.bfloat16),
                    gu_ref[...].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        g, u = h[:, :f], h[:, f:]
        a = g * jax.nn.sigmoid(g) * u
        o_ref[:m] = jnp.dot(a.astype(jnp.bfloat16),
                            dn_ref[...].astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)

    few = min(tm, 32)
    pl.when((rows > 0) & (rows <= few))(lambda: products(few))
    if few < tm:
        pl.when(rows > few)(lambda: products(tm))

    def add(r, carry):
        s = t * tm + r
        tok = pl.ds(tok_ref[s], 1)
        y_ref[tok, :] = y_ref[tok, :] + w_ref[s] * o_ref[pl.ds(r, 1), :]
        return carry

    lax.fori_loop(0, rows, add, 0)


def grouped_experts(rows, gate_up, down, tile_expert, tile_rows, n_tiles, tok,
                    w_slot, n):
    """``y[tok[s]] += w_slot[s] * (silu(r_s W_g) * (r_s W_u)) W_d`` over the
    slots in use, with the matrices of the slot's tile's expert: ``rows``
    (tiles * tm, D), ``gate_up`` (held, D, 2F), ``down`` (held, F, D); by
    tile ``tile_expert`` (ascending) and ``tile_rows`` (its rows in use, the
    tile's first), int32; ``n_tiles`` int32 scalar, the tiles in use; by
    slot ``tok`` int32 and ``w_slot`` float32. Returns (n, D) float32.

    The stacked arrays go in whole. The tables are prefetched and the weight
    blocks' index maps pick the expert's matrices in place: an expert with
    several tiles keeps its block index and is fetched once, one with no
    tile is never fetched, and the steps past the last tile in use repeat
    its indices (no bytes) and do nothing."""
    tiles = tile_expert.shape[0]
    tm = rows.shape[0] // tiles
    _, f, d = down.shape

    def tile(t, te, nt, *_):
        return (jnp.minimum(t, jnp.maximum(nt[0] - 1, 0)), 0)

    def expert(t, te, nt, *_):
        return (te[tile(t, te, nt)[0]], 0, 0)

    w = gate_up.dtype.itemsize
    return pl.pallas_call(
        _experts_kernel,
        name="mxtpu_experts_swiglu",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(tiles,),
            in_specs=[pl.BlockSpec((tm, d), tile),
                      pl.BlockSpec((None, d, 2 * f), expert),
                      pl.BlockSpec((None, f, d), expert)],
            out_specs=pl.BlockSpec((n, d), lambda t, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=6 * tiles * tm * d * f,
            bytes_accessed=3 * gate_up.shape[0] * d * f * w
            + tiles * tm * d * rows.dtype.itemsize + n * d * 4,
            transcendentals=tiles * tm * f),
        interpret=_interpret(),
        **_vmem_params(_experts_resident(n, tm, d, f, rows.dtype,
                                         gate_up.dtype)),
    )(tile_expert, n_tiles.reshape(1), tile_rows, tok, w_slot, rows, gate_up,
      down)


# ---------------------------------------------------------------------------
# routed experts too large to be resident whole: the same two grouped
# products, an expert taken in blocks along its inner width
# ---------------------------------------------------------------------------
_EXPERTS_VMEM = 84 * 2**20     # what either experts kernel may keep resident


def _experts_block_resident(tm, d, fb, x_dtype, w_dtype):
    """Bytes ``grouped_experts_blocked`` keeps in fast memory: a block of
    the expert's three matrices and a tile of rows, each double-buffered,
    the tile's float32 results (the output's block, two buffers) and the
    float32 products."""
    w = jnp.dtype(w_dtype).itemsize
    return (2 * 3 * d * fb * w + 2 * tm * d * jnp.dtype(x_dtype).itemsize
            + 2 * tm * d * 4 + 4 * tm * (3 * fb + d))


def experts_kernel_blocks(n, tm, d, f, x_dtype, w_dtype):
    """The routed layer's ONE rule, from what the op sees: ``0`` where
    ``grouped_experts`` takes the shapes with an expert whole
    (``experts_kernel_serves``); where an EXPERT is too large to be resident
    whole twice, the width ``fb`` of the blocks in which
    ``grouped_experts_blocked`` takes it along its inner width (the widest
    of 512, 256, 128 that divides it and fits); else None, the loop's: the
    CPU, odd widths, and experts that would fit but for the tokens' sums
    (4096 float32 rows of 2048), which stay where they were."""
    if experts_kernel_serves(n, tm, d, f, x_dtype, w_dtype):
        return 0
    sublanes = 32 // jnp.dtype(x_dtype).itemsize
    if not (_use_pallas() and d % 128 == 0 and tm % sublanes == 0) \
            or _experts_resident(0, tm, d, f, x_dtype, w_dtype) \
            <= _EXPERTS_VMEM:
        return None
    for fb in (512, 256, 128):
        if f % fb == 0 and _experts_block_resident(
                tm, d, fb, x_dtype, w_dtype) <= _EXPERTS_VMEM:
            return fb
    return None


def _experts_blocked_kernel(te_ref, nt_ref, rows_ref, x_ref, g_ref, u_ref,
                            dn_ref, o_ref):
    """Grid (tile, block of the inner width). ``x_ref`` (tm, D): the rows of
    one tile, all of one expert; ``g_ref``, ``u_ref`` (D, fb) and ``dn_ref``
    (fb, D): block j of that expert's gate, up and down matrices; ``o_ref``
    (tm, D) float32: the tile's results, resident over j, the sum of the
    blocks' products. As ``_experts_kernel``: both products on the matrix
    unit in float32 from bfloat16 operands, SwiGLU on the float32 product,
    rounded once. A tile past the last in use, or with no row in use, does
    nothing."""
    del te_ref                        # the index maps read it
    t, j = pl.program_id(0), pl.program_id(1)

    @pl.when((t < nt_ref[0]) & (rows_ref[t] > 0))
    def _products():
        x = x_ref[...].astype(jnp.bfloat16)
        g = jnp.dot(x, g_ref[...].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        u = jnp.dot(x, u_ref[...].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        a = (g * jax.nn.sigmoid(g) * u).astype(jnp.bfloat16)
        part = jnp.dot(a, dn_ref[...].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _first():
            o_ref[...] = part

        @pl.when(j > 0)
        def _more():
            o_ref[...] = o_ref[...] + part


def grouped_experts_blocked(rows, gate_up, down, tile_expert, tile_rows,
                            n_tiles, fb):
    """``(silu(r_s W_g) * (r_s W_u)) W_d`` for every slot of the tiles in
    use, with the matrices of the slot's tile's expert taken ``fb`` columns
    of the inner width at a time: ``rows`` (tiles * tm, D), ``gate_up``
    (held, D, 2F), ``down`` (held, F, D); by tile ``tile_expert``
    (ascending) and ``tile_rows`` (its rows in use), int32; ``n_tiles``
    int32 scalar, the tiles in use. Returns (tiles * tm, D) float32, NOT
    yet weighted or summed a token; the rows of tiles not in use hold
    whatever (the caller drops them).

    For experts that ``grouped_experts`` cannot hold whole (7168 x 4096 +
    2048 x 7168 in bfloat16 is 88 MB, twice resident 176): per grid step
    one block of each matrix is fetched, in place in the stacked arrays
    (``gate_up`` goes in twice, its index maps picking block j of the gate
    half and block j of the up half). Every block of a touched expert is
    read once a TILE of that expert, so an expert with several tiles (a long
    prefill) is read once a tile; an expert no token chose has no tile and
    costs no bytes; the steps past the last tile in use repeat the last
    indices (no bytes) and do nothing."""
    tiles = tile_expert.shape[0]
    tm = rows.shape[0] // tiles
    _, f, d = down.shape
    nf = f // fb

    def tile(t, j, te, nt, *_):
        return (jnp.minimum(t, jnp.maximum(nt[0] - 1, 0)), 0)

    def block(t, j, nt):
        return jnp.where(t < nt[0], j, nf - 1)

    def gate(t, j, te, nt, *_):
        return (te[tile(t, j, te, nt)[0]], 0, block(t, j, nt))

    def up(t, j, te, nt, *_):
        return (te[tile(t, j, te, nt)[0]], 0, nf + block(t, j, nt))

    def dn(t, j, te, nt, *_):
        return (te[tile(t, j, te, nt)[0]], block(t, j, nt), 0)

    w = gate_up.dtype.itemsize
    return pl.pallas_call(
        _experts_blocked_kernel,
        name="mxtpu_experts_swiglu_blocked",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles, nf),
            in_specs=[pl.BlockSpec((tm, d), tile),
                      pl.BlockSpec((None, d, fb), gate),
                      pl.BlockSpec((None, d, fb), up),
                      pl.BlockSpec((None, fb, d), dn)],
            out_specs=pl.BlockSpec((tm, d), tile)),
        out_shape=jax.ShapeDtypeStruct((tiles * tm, d), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=6 * tiles * tm * d * f,
            bytes_accessed=3 * gate_up.shape[0] * d * f * w
            + tiles * tm * d * (rows.dtype.itemsize + 4),
            transcendentals=tiles * tm * f),
        interpret=_interpret(),
        **_vmem_params(_experts_block_resident(tm, d, fb, rows.dtype,
                                               gate_up.dtype)),
    )(tile_expert, n_tiles.reshape(1), tile_rows, rows, gate_up, gate_up,
      down)


# ---------------------------------------------------------------------------
# latent (MLA) decode attention, absorbed: every query head of a slot against
# the ONE latent row a position its pages hold, values the row's leading
# columns, so a page is read once for keys and values
# ---------------------------------------------------------------------------
def mla_decode_attention(q, pool, layer, page_table, positions, value_dim,
                         scale):
    """Absorbed multi-head latent attention straight from a latent pool.

    q : (S, K, H, R) — the ABSORBED queries ``[W_UK,h q_h^nope | q_h^rope]``;
        query k of slot s stands at ``positions[s] + k``.
    pool : [num_pages, layers, 1, R, page_tokens] — a position's row is
        ``[c (value_dim) | k_rope]``, shared by all H heads; a page of one
        layer is one contiguous block, positions along the lanes.
    layer, page_table (S, W+1), positions (S,): as ``paged_decode_attention``.

    ``score = scale * q_h . row``, softmax over the positions ``<=
    positions[s] + k`` in mapped pages, ``out_h = sum_t p_t row_t[:value_dim]``
    (the caller expands it with ``W_UV``). Returns (S, K, H, value_dim) in
    q's dtype; scores, softmax and sums in float32. A slot with no mapped
    page returns zeros.

    Pallas kernel ``mxtpu_mla_decode`` on TPU (both products on the matrix
    unit: H queries a row make it a matrix problem); the gather + mask +
    softmax of the same numbers elsewhere."""
    r, p = pool.shape[-2:]
    layer = jnp.asarray(layer, jnp.int32)
    page_table = page_table.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    if _use_pallas() and p % 128 == 0 and r % 8 == 0 and value_dim % 128 == 0:
        return _mla_decode_tpu(q, pool, layer, page_table, positions,
                               int(value_dim), float(scale))
    return _mla_decode_reference(q, pool, layer, page_table, positions,
                                 int(value_dim), float(scale))


def _mla_decode_reference(q, pool, layer, page_table, positions, value_dim,
                          scale):
    """The plain body: gather every column of every slot's table row into a
    (S, W*P, R) view, mask, softmax, weigh."""
    num_pages, _, _, r, p = pool.shape
    s, kq, h, _ = q.shape
    w = page_table.shape[1] - 1
    ids = page_table[:, :w]
    kpos = jnp.arange(w * p, dtype=jnp.int32)
    qpos = positions[:, None] + jnp.arange(kq, dtype=jnp.int32)   # (S, K)
    ok = (kpos[None, None, :] <= qpos[:, :, None]) \
        & jnp.repeat(ids < num_pages, p, axis=1)[:, None, :]      # (S,K,WP)
    rows = pool[ids.reshape(-1), layer, 0]          # (S*W, R, P); clamps
    rows = rows.reshape(s, w, r, p).transpose(0, 1, 3, 2).reshape(s, w * p, r)
    # the last query sees the most: what none may see reads as zero
    rows = jnp.where(ok[:, -1][:, :, None], rows, 0)
    logits = jnp.einsum("skhr,str->skht", q, rows,
                        preferred_element_type=jnp.float32) * scale
    mask = ok[:, :, None, :]
    logits = jnp.where(mask, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(logits - m), 0.0)
    probs = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("skht,stv->skhv", probs.astype(rows.dtype),
                     rows[..., :value_dim],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _mla_decode_kernel(tab_ref, pos_ref, lay_ref, q_ref, pool_ref, o_ref,
                       buf_ref, sem, half_ref, *, num_pages, heads, value_dim,
                       scale, pages_a_step):
    """Grid (slot,). ``q_ref`` (K*H, R): the slot's absorbed queries, query
    k's heads in rows k*H ..; ``pool_ref`` the whole pool where it lies;
    ``o_ref`` (K*H, value_dim). A loop over the slot's live pages only, in
    groups of ``pages_a_step``: each group is copied into one half of
    ``buf_ref`` (2, n, R, P) while the group before it is weighed from the
    other, and a slot's last group starts the next slot's first;
    ``half_ref`` (1,) holds the half the next group to weigh lies in.
    Online softmax in float32, updated once a group. Scores ``q @ page``
    and sums ``p @ page[:value_dim]^T`` run on the matrix unit in the
    pool's type."""
    si, slots = pl.program_id(0), pl.num_programs(0)
    n = pages_a_step
    kh, p = q_ref.shape[0], buf_ref.shape[-1]
    kq = kh // heads
    w = tab_ref.shape[1] - 1

    def live_pages(s):              # the pages holding positions <= newest
        return jnp.minimum((pos_ref[s] + kq - 1) // p + 1, w)

    def copies(s, g, half):
        """(mapped, copy) for each page of group ``g`` of slot ``s``."""
        out = []
        for i in range(n):
            j = g * n + i
            page = tab_ref[s, jnp.minimum(j, w - 1)]
            out.append(((j < live_pages(s)) & (page < num_pages),
                        pltpu.make_async_copy(
                            pool_ref.at[jnp.minimum(page, num_pages - 1),
                                        lay_ref[0], 0],
                            buf_ref.at[half, i], sem.at[half, i])))
        return out

    def start(s, g, half):
        for mapped, copy in copies(s, g, half):
            pl.when(mapped)(copy.start)

    @pl.when(si == 0)
    def _first():
        half_ref[0] = 0
        start(0, 0, 0)

    pos = pos_ref[si]
    last = pos + kq - 1               # the newest position any query sees
    # at least one group, even where nothing is live: its last group hands
    # the copies on to the next slot
    groups = jnp.maximum((live_pages(si) + n - 1) // n, 1)
    qk = jax.lax.broadcasted_iota(jnp.int32, (kh, 1), 0) // heads

    def weigh(g, carry):
        m_prev, l_prev, acc = carry
        half = half_ref[0]

        @pl.when(g + 1 < groups)
        def _next_group():
            start(si, g + 1, 1 - half)

        @pl.when((g + 1 == groups) & (si + 1 < slots))
        def _next_slot():
            start(si + 1, 0, 1 - half)

        oks, ss, pages = [], [], []
        for i, (mapped, copy) in enumerate(copies(si, g, half)):
            pl.when(mapped)(copy.wait)
            kpos = (g * n + i) * p \
                + jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)
            # the newest position this page may show; -1 if it shows none
            seen = jnp.where(mapped, last, -1)
            # past the slot's length, or in a page not copied, a buffer may
            # hold anything: 0 x NaN
            page = jnp.where(kpos <= seen, buf_ref[half, i], 0)   # (R, P)
            ok = kpos <= jnp.minimum(pos + qk, seen)              # (KH, P)
            s = jnp.dot(q_ref[...], page,
                        preferred_element_type=jnp.float32) * scale
            oks.append(ok)
            ss.append(jnp.where(ok, s, _NEG_INF))
            pages.append(page)
        m_new = functools.reduce(
            jnp.maximum, [jnp.max(s, axis=-1, keepdims=True) for s in ss],
            m_prev)
        alpha = jnp.exp(m_prev - m_new)
        l_new, acc = l_prev * alpha, acc * alpha
        for ok, s, page in zip(oks, ss, pages):
            pr = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_new = l_new + jnp.sum(pr, axis=-1, keepdims=True)
            acc = acc + jax.lax.dot_general(
                pr.astype(page.dtype), page[:value_dim],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        half_ref[0] = 1 - half
        return m_new, l_new, acc

    _, l, acc = jax.lax.fori_loop(
        0, groups, weigh,
        (jnp.full((kh, 1), _NEG_INF, jnp.float32),
         jnp.zeros((kh, 1), jnp.float32),
         jnp.zeros((kh, value_dim), jnp.float32)))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


_MLA_PAGES_A_STEP = 4   # pages weighed a softmax update


def _mla_decode_tpu(q, pool, layer, page_table, positions, value_dim, scale):
    num_pages, _, _, r, p = pool.shape
    s, kq, h, _ = q.shape
    w = page_table.shape[1] - 1
    n = min(_MLA_PAGES_A_STEP, w)

    def slot(si, tab, pos, lay):
        return (si, 0, 0)

    itemsize = pool.dtype.itemsize
    out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, num_pages=num_pages, heads=h,
                          value_dim=value_dim, scale=scale, pages_a_step=n),
        name="mxtpu_mla_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s,),
            in_specs=[pl.BlockSpec((None, kq * h, r), slot),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, kq * h, value_dim), slot),
            scratch_shapes=[
                pltpu.VMEM((2, n, r, p), pool.dtype),            # pages
                pltpu.SemaphoreType.DMA((2, n)),
                pltpu.SMEM((1,), jnp.int32),                     # half
            ]),
        # one slot after another: a slot's last group starts the next's copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((s, kq * h, value_dim), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * s * kq * h * (r + value_dim) * w * p,
            bytes_accessed=s * w * r * p * itemsize,
            transcendentals=s * kq * h * w * p),
        interpret=_interpret(),
    )(page_table, positions, layer.reshape(1),
      q.reshape(s, kq * h, r).astype(pool.dtype), pool)
    return out.reshape(s, kq, h, value_dim)
