"""Neural-network operators (the npx.* surface backing Gluon layers).

TPU-native equivalent of src/operator/nn/* (conv, FC, BN, LN, GN, pooling,
softmax, dropout, activation) and src/operator/contrib/transformer.cc
(attention projections). Design notes:

- Convs/matmuls lower to lax.conv_general_dilated / jnp.matmul → MXU. The
  reference's cuDNN algo autotuning (src/operator/nn/cudnn/) has no analog:
  XLA picks the conv emitter.
- BatchNorm is functional: in training mode it RETURNS updated running stats
  (out, new_mean, new_var) and the Gluon layer writes them back; the moving
  stats are stop_gradient'ed (the reference mutates aux states in-kernel).
- Dropout is an rng op (needs_rng): the PRNG key is threaded in by the
  registry; under CachedOp the key becomes an explicit input so every compiled
  call gets fresh randomness (the reference used per-op random resources,
  include/mxnet/resource.h:39).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import register


# ---------------------------------------------------------------------------
# fully connected — reference: src/operator/nn/fully_connected.cc
# ---------------------------------------------------------------------------
@register("fully_connected")
def _fc(no_bias=False, flatten=True, num_hidden=0):
    def f(x, w, *b):
        if flatten and x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        y = jnp.matmul(x, w.T)
        if not no_bias:
            y = y + b[0]
        return y

    return f


# ---------------------------------------------------------------------------
# convolution — reference: src/operator/nn/convolution.cc
# ---------------------------------------------------------------------------
def _conv_dnums(ndim, layout):
    if layout is None:
        layout = {3: "NCW", 4: "NCHW", 5: "NCDHW"}[ndim]
    spatial = layout[2:] if layout[1] == "C" else layout[1:-1]
    rhs = "OI" + spatial
    return layout, rhs, layout


@register("convolution")
def _convolution(kernel=(), stride=(), dilate=(), pad=(), num_filter=0,
                 num_group=1, no_bias=False, layout=None):
    def f(x, w, *b):
        nd = x.ndim
        lhs_l, rhs_l, out_l = _conv_dnums(nd, layout)
        nsp = nd - 2
        strides = tuple(stride) if stride else (1,) * nsp
        dil = tuple(dilate) if dilate else (1,) * nsp
        pads = tuple(pad) if pad else (0,) * nsp
        # no preferred_element_type: the MXU accumulates bf16 convs in f32
        # internally, and a widened output dtype breaks the conv transpose
        # rule under grad
        y = lax.conv_general_dilated(
            x, w,
            window_strides=strides,
            padding=[(p, p) for p in pads],
            rhs_dilation=dil,
            dimension_numbers=(lhs_l, rhs_l, out_l),
            feature_group_count=num_group,
        )
        if not no_bias:
            c_axis = out_l.index("C")
            bshape = [1] * nd
            bshape[c_axis] = b[0].shape[0]
            y = y + b[0].reshape(bshape)
        return y

    return f


@register("deconvolution")
def _deconvolution(kernel=(), stride=(), dilate=(), pad=(), adj=(),
                   num_filter=0, num_group=1, no_bias=False, layout=None):
    def f(x, w, *b):
        if num_group != 1:
            # grouped transpose conv: split channels, run per group, concat
            # (lax.conv_transpose has no feature_group_count)
            lhs_l, _, out_l = _conv_dnums(x.ndim, layout)
            c_axis = lhs_l.index("C")
            xs = jnp.split(x, num_group, axis=c_axis)
            ws = jnp.split(w, num_group, axis=0)
            parts = [_deconv_one(xi, wi, (), kernel, stride, dilate, pad,
                                 adj, True, layout)
                     for xi, wi in zip(xs, ws)]
            y = jnp.concatenate(parts, axis=out_l.index("C"))
            if not no_bias:
                bshape = [1] * x.ndim
                bshape[out_l.index("C")] = b[0].shape[0]
                y = y + b[0].reshape(bshape)
            return y
        return _deconv_one(x, w, b, kernel, stride, dilate, pad, adj,
                           no_bias, layout)

    return f


def _deconv_one(x, w, b, kernel, stride, dilate, pad, adj, no_bias, layout):
    nd = x.ndim
    lhs_l, rhs_l, out_l = _conv_dnums(nd, layout)
    nsp = nd - 2
    strides = tuple(stride) if stride else (1,) * nsp
    pads = tuple(pad) if pad else (0,) * nsp
    adjs = tuple(adj) if adj else (0,) * nsp
    dil = tuple(dilate) if dilate else (1,) * nsp
    k = tuple(kernel)
    # MXNet semantics: out = (in-1)*s + d*(k-1) + 1 - 2p + adj
    # lax explicit padding pads the stride-dilated input directly:
    # out = (in-1)*s + 1 + pl + ph - k_eff + 1 with k_eff = d*(k-1)+1
    # => pl = k_eff - 1 - p, ph = pl + adj
    keff = [dil[i] * (k[i] - 1) + 1 for i in range(nsp)]
    padding = [(keff[i] - 1 - pads[i], keff[i] - 1 - pads[i] + adjs[i])
               for i in range(nsp)]
    y = lax.conv_transpose(
        x, w,
        strides=strides,
        padding=padding,
        rhs_dilation=dil,
        dimension_numbers=(lhs_l, rhs_l, out_l),
        transpose_kernel=True,
    )
    if not no_bias:
        c_axis = out_l.index("C")
        bshape = [1] * nd
        bshape[c_axis] = b[0].shape[0]
        y = y + b[0].reshape(bshape)
    return y


# ---------------------------------------------------------------------------
# pooling — reference: src/operator/nn/pooling.cc
# ---------------------------------------------------------------------------
@register("pooling")
def _pooling(kernel=(), pool_type="max", stride=(), pad=(), global_pool=False,
             count_include_pad=True, layout=None, ceil_mode=False):
    def f(x):
        nd = x.ndim
        lay = layout or {3: "NCW", 4: "NCHW", 5: "NCDHW"}[nd]
        sp_axes = tuple(i for i, c in enumerate(lay) if c not in "NC")
        if global_pool:
            if pool_type == "max":
                return jnp.max(x, axis=sp_axes, keepdims=True)
            return jnp.mean(x, axis=sp_axes, keepdims=True)
        nsp = len(sp_axes)
        k = tuple(kernel)
        strides = tuple(stride) if stride else (1,) * nsp
        pads = tuple(pad) if pad else (0,) * nsp
        wdims = [1] * nd
        wstr = [1] * nd
        wpad = [(0, 0)] * nd
        for i, ax in enumerate(sp_axes):
            wdims[ax] = k[i]
            wstr[ax] = strides[i]
            extra = 0
            if ceil_mode:
                # include the last partial window (reference pooling.cc
                # ceil rounding): pad right so the window grid covers it
                span = x.shape[ax] + 2 * pads[i] - k[i]
                rem = span % strides[i]
                if rem:
                    extra = strides[i] - rem
            wpad[ax] = (pads[i], pads[i] + extra)
        if pool_type == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
                jnp.iinfo(x.dtype).min
            return lax.reduce_window(x, init, lax.max, wdims, wstr, wpad)
        s = lax.reduce_window(x, 0.0, lax.add, wdims, wstr, wpad)
        has_extra = any(hi != lo for lo, hi in wpad)
        if count_include_pad and not has_extra:
            # constant divisor fast path (the default config)
            denom = 1
            for i in range(nsp):
                denom *= k[i]
            return s / denom
        # divisor (reference pool.h:468-479): symmetric padding counts when
        # count_include_pad, but the ceil-mode extra region NEVER does — so
        # count window positions over a mask that is 1 on data (+sym pad if
        # include_pad) and 0 on the ceil extra
        ones = jnp.ones(x.shape, jnp.float32)
        if count_include_pad:
            mask_pad = [(lo, lo) for lo, _ in wpad]  # symmetric part = 1s
            ones = jnp.pad(ones, mask_pad, constant_values=1.0)
            extra_pad = [(0, hi - lo) for lo, hi in wpad]
            cnt = lax.reduce_window(ones, 0.0, lax.add, wdims, wstr,
                                    extra_pad)
        else:
            cnt = lax.reduce_window(ones, 0.0, lax.add, wdims, wstr, wpad)
        return s / cnt.astype(s.dtype)

    return f


@register("adaptive_avg_pool2d")
def _adaptive_avg_pool2d(output_size=1):
    osz = (output_size, output_size) if isinstance(output_size, int) \
        else tuple(output_size)

    def f(x):  # NCHW
        n, c, h, w = x.shape
        if osz == (1, 1):
            return jnp.mean(x, axis=(2, 3), keepdims=True)
        if h % osz[0] == 0 and w % osz[1] == 0:
            x = x.reshape(n, c, osz[0], h // osz[0], osz[1], w // osz[1])
            return jnp.mean(x, axis=(3, 5))
        raise MXNetError("adaptive_avg_pool2d requires divisible sizes on TPU")

    return f


# ---------------------------------------------------------------------------
# normalization — reference: nn/batch_norm.cc, nn/layer_norm.cc, nn/group_norm.cc
# ---------------------------------------------------------------------------
@register("batch_norm")
def _batch_norm(eps=1e-5, momentum=0.9, fix_gamma=True, use_batch_stats=True,
                axis=1):
    def f(x, gamma, beta, moving_mean, moving_var):
        g = jnp.ones_like(gamma) if fix_gamma else gamma
        ax = axis if axis >= 0 else x.ndim + axis  # normalize negative axis
        red = tuple(i for i in range(x.ndim) if i != ax)
        shape = [1] * x.ndim
        shape[ax] = x.shape[ax]
        if use_batch_stats:
            mean = jnp.mean(x, axis=red)
            var = jnp.var(x, axis=red)
            new_mean = lax.stop_gradient(
                momentum * moving_mean + (1 - momentum) * mean)
            new_var = lax.stop_gradient(
                momentum * moving_var + (1 - momentum) * var)
        else:
            mean, var = moving_mean, moving_var
            new_mean, new_var = moving_mean, moving_var
        inv = lax.rsqrt(var.astype(jnp.float32) + eps).astype(x.dtype)
        out = (x - mean.reshape(shape).astype(x.dtype)) * \
            (g * inv).reshape(shape).astype(x.dtype) + \
            beta.reshape(shape).astype(x.dtype)
        return out, new_mean, new_var

    return f


@register("layer_norm")
def _layer_norm(axis=-1, eps=1e-5):
    def f(x, gamma, beta):
        ax = axis if axis >= 0 else x.ndim + axis
        if ax == x.ndim - 1:
            # fused row-norm kernel on TPU (Pallas), XLA formula elsewhere
            from .pallas_kernels import fused_layer_norm

            return fused_layer_norm(x, gamma, beta, eps)
        mean = jnp.mean(x, axis=axis, keepdims=True)
        var = jnp.var(x, axis=axis, keepdims=True)
        inv = lax.rsqrt(var.astype(jnp.float32) + eps).astype(x.dtype)
        shape = [1] * x.ndim
        shape[ax] = x.shape[ax]
        return (x - mean) * inv * gamma.reshape(shape) + beta.reshape(shape)

    return f


@register("group_norm")
def _group_norm(num_groups=1, eps=1e-5):
    def f(x, gamma, beta):  # NC...
        n, c = x.shape[0], x.shape[1]
        rest = x.shape[2:]
        xg = x.reshape(n, num_groups, c // num_groups, *rest)
        red = tuple(range(2, xg.ndim))
        mean = jnp.mean(xg, axis=red, keepdims=True)
        var = jnp.var(xg, axis=red, keepdims=True)
        xg = (xg - mean) * lax.rsqrt(var + eps)
        out = xg.reshape(x.shape)
        shape = [1] * x.ndim
        shape[1] = c
        return out * gamma.reshape(shape) + beta.reshape(shape)

    return f


@register("instance_norm")
def _instance_norm(eps=1e-5):
    def f(x, gamma, beta):  # NC...
        red = tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=red, keepdims=True)
        var = jnp.var(x, axis=red, keepdims=True)
        shape = [1] * x.ndim
        shape[1] = x.shape[1]
        return (x - mean) * lax.rsqrt(var + eps) * gamma.reshape(shape) + \
            beta.reshape(shape)

    return f


@register("rms_norm")
def _rms_norm(axis=-1, eps=1e-6):
    def f(x, gamma):
        # statistics AND the scaling in float32, rounded once at the end
        # (float32 x: the program it always was)
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=axis, keepdims=True)
        return ((xf * lax.rsqrt(ms + eps)) * gamma).astype(
            jnp.promote_types(x.dtype, gamma.dtype))

    return f


# ---------------------------------------------------------------------------
# activations — reference: nn/activation.cc, leaky_relu.cc
# ---------------------------------------------------------------------------
@register("activation")
def _activation(act_type="relu"):
    table = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
        "log_sigmoid": jax.nn.log_sigmoid,
        "mish": jax.nn.mish,
        # reference HardSigmoid (leaky_relu.cc): clip(0.2*x + 0.5, 0, 1) —
        # NOT jax.nn.hard_sigmoid, whose slope is 1/6
        "hard_sigmoid": lambda x: jnp.clip(0.2 * x + 0.5, 0.0, 1.0),
        "hard_swish": jax.nn.hard_swish,
        "silu": jax.nn.silu,
    }
    if act_type not in table:
        raise MXNetError(f"unknown activation {act_type!r}")
    return table[act_type]


@register("leaky_relu")
def _leaky_relu(act_type="leaky", slope=0.25):
    if act_type == "leaky":
        return lambda x: jax.nn.leaky_relu(x, slope)
    if act_type == "elu":
        return lambda x: jax.nn.elu(x, slope)
    if act_type == "selu":
        return jax.nn.selu
    if act_type == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=False)
    if act_type == "gelu_tanh":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if act_type == "prelu":
        return lambda x, alpha: jnp.where(x >= 0, x, alpha * x)
    raise MXNetError(f"unknown leaky_relu variant {act_type!r}")


@register("softmax")
def _softmax(axis=-1, temperature=None, use_length=False):
    def f(x, *length):
        z = x / temperature if temperature not in (None, 1.0) else x
        if use_length:
            mask = _length_mask(x, length[0], axis)
            z = jnp.where(mask, z, -jnp.inf)
        return jax.nn.softmax(z, axis=axis)

    return f


@register("log_softmax")
def _log_softmax(axis=-1, temperature=None):
    def f(x):
        z = x / temperature if temperature not in (None, 1.0) else x
        return jax.nn.log_softmax(z, axis=axis)

    return f


@register("masked_softmax")
def _masked_softmax(axis=-1, temperature=1.0):
    def f(x, mask):
        z = x / temperature if temperature != 1.0 else x
        z = jnp.where(mask.astype(bool), z, -jnp.inf)
        out = jax.nn.softmax(z, axis=axis)
        return jnp.where(mask.astype(bool), out, 0.0)

    return f


def _length_mask(x, length, axis):
    ax = axis if axis >= 0 else x.ndim + axis
    idx = jnp.arange(x.shape[ax])
    shape = [1] * x.ndim
    shape[ax] = x.shape[ax]
    idx = idx.reshape(shape)
    lshape = [1] * x.ndim
    lshape[0] = x.shape[0]
    return idx < length.reshape(lshape)


# ---------------------------------------------------------------------------
# dropout — reference: nn/dropout.cc (rng resource -> explicit key input)
# ---------------------------------------------------------------------------
@register("dropout", needs_rng=True)
def _dropout(p=0.5, mode="training", training=True):
    def f(key, x):
        if not training or p <= 0.0:
            return x
        keep = 1.0 - p
        mask = jax.random.bernoulli(key, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)

    return f


# ---------------------------------------------------------------------------
# embedding / sequence — reference: indexing_op.cc (Embedding), sequence_*.cc
# ---------------------------------------------------------------------------
@register("embedding")
def _embedding(input_dim=0, output_dim=0, sparse_grad=False):
    def f(idx, weight):
        return jnp.take(weight, idx.astype(jnp.int32), axis=0)

    return f


@register("sequence_mask")
def _sequence_mask(use_sequence_length=False, value=0.0, axis=0):
    def f(x, *length):
        if not use_sequence_length:
            return x
        seq_ax = axis
        idx = jnp.arange(x.shape[seq_ax])
        shape = [1] * x.ndim
        shape[seq_ax] = x.shape[seq_ax]
        idx = idx.reshape(shape)
        batch_ax = 1 - seq_ax
        lshape = [1] * x.ndim
        lshape[batch_ax] = x.shape[batch_ax]
        mask = idx < length[0].reshape(lshape)
        return jnp.where(mask, x, value)

    return f


@register("sequence_reverse")
def _sequence_reverse(use_sequence_length=False, axis=0):
    def f(x, *length):
        if not use_sequence_length:
            return jnp.flip(x, axis=axis)
        # per-example reverse of the first `length` steps (seq axis 0)
        T = x.shape[0]
        t = jnp.arange(T)[:, None]
        ln = length[0][None, :].astype(jnp.int32)
        src = jnp.where(t < ln, ln - 1 - t, t)  # (T, B)
        b = jnp.arange(x.shape[1])[None, :]
        return x[src, b]

    return f


@register("sequence_last")
def _sequence_last(use_sequence_length=False, axis=0):
    def f(x, *length):
        if not use_sequence_length:
            return x[-1] if axis == 0 else jnp.take(x, x.shape[axis] - 1, axis)
        idx = (length[0].astype(jnp.int32) - 1)  # (B,)
        b = jnp.arange(x.shape[1])
        return x[idx, b]

    return f


# ---------------------------------------------------------------------------
# losses / misc — reference: smooth_l1, pick (indexing_op.cc)
# ---------------------------------------------------------------------------
@register("pick")
def _pick(axis=-1, keepdims=False, mode="clip"):
    def f(x, idx):
        i = jnp.expand_dims(idx.astype(jnp.int32), axis)
        out = jnp.take_along_axis(x, i, axis=axis)
        return out if keepdims else jnp.squeeze(out, axis)

    return f


@register("smooth_l1")
def _smooth_l1(scalar=1.0):
    def f(x):
        s2 = scalar * scalar
        return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * x * x,
                         jnp.abs(x) - 0.5 / s2)

    return f


@register("ctc_loss")
def _ctc_loss(use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    import optax

    def f(data, label, *lens):
        # data: (T, B, V) logits; label: (B, L)
        logits = jnp.transpose(data, (1, 0, 2))  # (B, T, V)
        B, T, V = logits.shape
        i = 0
        if use_data_lengths:
            dl = lens[i].astype(jnp.int32)
            i += 1
        else:
            dl = jnp.full((B,), T, jnp.int32)
        if use_label_lengths:
            ll = lens[i].astype(jnp.int32)
        else:
            ll = jnp.sum((label >= 0) & (label != 0), axis=-1).astype(jnp.int32) \
                if blank_label == "first" else \
                jnp.sum(label >= 0, axis=-1).astype(jnp.int32)
        t = jnp.arange(T)[None, :]
        logit_pad = (t >= dl[:, None]).astype(jnp.float32)
        L = label.shape[1]
        lt = jnp.arange(L)[None, :]
        label_pad = (lt >= ll[:, None]).astype(jnp.float32)
        lab = label.astype(jnp.int32)
        if blank_label == "first":
            blank_id = 0
        else:
            blank_id = V - 1
        return optax.ctc_loss(logits, logit_pad, lab, label_pad,
                              blank_id=blank_id)

    return f


# attention — reference: src/operator/contrib/transformer.cc. The unmasked
# path routes through the Pallas flash-attention kernel (online softmax,
# no O(T^2) materialization); arbitrary masks use the XLA path.
@register("multihead_attention")
def _multihead_attention(num_heads=1, dropout=0.0, causal=False, scale=None,
                         num_kv_heads=None):
    """``num_kv_heads`` (beyond the reference): grouped-query / multi-query
    attention — k/v carry ``num_kv_heads`` heads, each shared by
    ``num_heads // num_kv_heads`` query heads (the modern LLM KV-cache
    shrink). Default None = classic MHA."""
    n_kv = num_heads if num_kv_heads is None else int(num_kv_heads)
    if n_kv < 1 or num_heads % n_kv:
        raise MXNetError(
            f"num_kv_heads must be a positive divisor of num_heads "
            f"{num_heads}, got {num_kv_heads}")

    def f(q, k, v, *mask):
        # q: (B, T, num_heads*D); k/v: (B, T, n_kv*D)
        B, Tq, E = q.shape
        Tk = k.shape[1]
        D = E // num_heads
        qh = q.reshape(B, Tq, num_heads, D).transpose(0, 2, 1, 3)
        kh = k.reshape(B, Tk, n_kv, D).transpose(0, 2, 1, 3)
        vh = v.reshape(B, Tk, n_kv, D).transpose(0, 2, 1, 3)
        if n_kv != num_heads:
            # materializing stopgap: the repeat restores (B, H, T, D) for
            # the shared kernels; the GQA input/KV-cache stays n_kv-sized,
            # but attention-time KV traffic matches MHA until the Pallas
            # kernel grows a native grouped-heads mode (XLA typically folds
            # the broadcast into the batched matmul on the dense path)
            reps = num_heads // n_kv
            kh = jnp.repeat(kh, reps, axis=1)
            vh = jnp.repeat(vh, reps, axis=1)
        s = scale if scale is not None else 1.0 / (D ** 0.5)
        if not mask:
            from .pallas_kernels import flash_attention

            out = flash_attention(qh, kh, vh, s, causal)
        elif mask[0].ndim == 4 and mask[0].shape[1] == 1 and \
                mask[0].shape[2] == 1 and mask[0].shape[0] == B and \
                mask[0].shape[3] == Tk and Tq == Tk:
            # key-padding mask (B, 1, 1, Tk), constant over heads and
            # queries: express as segment ids (valid=its mask value,
            # padding=0) and stay on the fused flash path. Matches the
            # dense-mask branch for every row with >=1 valid key; a fully
            # masked row emits zeros here vs ~uniform softmax there
            # (documented in npx.multihead_attention)
            from .pallas_kernels import flash_attention

            seg = (mask[0].reshape(B, Tk) != 0).astype(jnp.int32)
            out = flash_attention(qh, kh, vh, s, causal,
                                  q_segment_ids=jnp.ones_like(seg),
                                  kv_segment_ids=seg)
        else:
            logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
            if causal:
                # bottom-right aligned (decode with cached KV: Tk >= Tq)
                cm = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
                logits = jnp.where(cm, logits, -1e30)
            logits = jnp.where(mask[0].astype(bool), logits, -1e30)
            w = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhqk,bhkd->bhqd", w, vh)
        return out.transpose(0, 2, 1, 3).reshape(B, Tq, E)

    return f


# decode attention against the paged KV pool — the pages a slot's table row
# maps are read where they lie (Pallas on TPU), no view is gathered.
@register("paged_decode_attention", differentiable=False)
def _paged_decode_attention(scale=None):
    """``q`` (S, K, Hq, D), the pools [pages, layers, Hkv, D, page_tokens],
    ``layer`` (int32 scalar operand), ``page_table`` (S, W+1), ``positions``
    (S,) -> (S, K, Hq*D). Grouped heads follow from the shapes (Hq a
    multiple of Hkv). Two more operands, ``k``, ``v`` (S, 1, Hkv, D): a
    K = 1 tick's new rows, stored before the query attends -> (out, k_pool,
    v_pool). See ``pallas_kernels.paged_decode_attention``."""
    def f(q, k_pool, v_pool, layer, page_table, positions, *rows):
        from .pallas_kernels import paged_decode_attention

        out = paged_decode_attention(q, k_pool, v_pool, layer, page_table,
                                     positions, scale, *rows)
        if not rows:
            return out.reshape(out.shape[:2] + (-1,))
        return (out[0].reshape(out[0].shape[:2] + (-1,)),) + out[1:]

    return f


# absorbed latent (MLA) decode attention against the latent pool: one row a
# position shared by every head, its leading columns the values.
@register("mla_decode_attention", differentiable=False)
def _mla_decode_attention(value_dim=None, scale=1.0):
    """``q`` (S, K, H, R) absorbed queries, the latent pool [pages, layers,
    1, R, page_tokens], ``layer`` (int32 scalar operand), ``page_table`` (S,
    W+1), ``positions`` (S,) -> (S, K, H*value_dim). See
    ``pallas_kernels.mla_decode_attention``."""
    def f(q, pool, layer, page_table, positions):
        from .pallas_kernels import mla_decode_attention

        out = mla_decode_attention(q, pool, layer, page_table, positions,
                                   int(value_dim), float(scale))
        return out.reshape(out.shape[:2] + (-1,))

    return f


@register("rope")
def _rope(rotary_dim=None, theta=10000.0, offset=0, inv_freq=None):
    """Rotary position embedding, rotate-half convention, on the first
    ``rotary_dim`` entries of the last axis (default: all of it); the rest
    passes through. ``x``: (B, T, H, D); position t is ``offset + t``, or,
    with a second operand ``positions`` (B or 1, T) int32, whatever that
    says A ROW (a decode tick's slots stand at different positions).
    ``inv_freq``: the ``rotary_dim / 2`` inverse frequencies, given (YaRN's
    blend is not a power of ``theta``); default ``theta ** (-i / half)``.
    Angles, cos and sin and the rotation itself are float32 whatever ``x``
    is; the result is ``x``'s type."""
    def f(x, *positions):
        D, T = x.shape[-1], x.shape[1]
        rot = D if rotary_dim is None else int(rotary_dim)
        if rot % 2 or not 0 < rot <= D:
            raise MXNetError(f"rope: rotary_dim {rot} is not an even number "
                             f"in (0, {D}]")
        half = rot // 2
        if inv_freq is None:
            inv = float(theta) ** (-jnp.arange(half, dtype=jnp.float32)
                                   / half)
        else:
            if len(inv_freq) != half:
                raise MXNetError(f"rope: {len(inv_freq)} inverse frequencies "
                                 f"for rotary_dim {rot}")
            inv = jnp.asarray(inv_freq, jnp.float32)
        f32 = jnp.float32
        if positions:
            # whole-width form: x * [cos | cos] + [-x2 | x1] * [sin | sin],
            # one convert at the end. (The halves converted and
            # concatenated, as below, abort the TPU compiler at a tick's
            # shapes in bfloat16: ``IsFusibleUnalignedDUS``.)
            ang = positions[0].astype(f32)[:, :, None] * inv
            cos = jnp.tile(jnp.cos(ang), 2)[:, :, None]
            sin = jnp.tile(jnp.sin(ang), 2)[:, :, None] * jnp.asarray(
                [-1.0] * half + [1.0] * half, f32)
            xr = x[..., :rot].astype(f32)
            out = (xr * cos + jnp.roll(xr, half, axis=-1) * sin) \
                .astype(x.dtype)
            return out if rot == D else jnp.concatenate(
                [out, x[..., rot:]], axis=-1)
        ang = (jnp.arange(T, dtype=f32) + offset)[:, None] * inv
        cos = jnp.cos(ang)[None, :, None]
        sin = jnp.sin(ang)[None, :, None]
        x1, x2 = x[..., :half].astype(f32), x[..., half:rot].astype(f32)
        return jnp.concatenate(
            [(x1 * cos - x2 * sin).astype(x.dtype),
             (x2 * cos + x1 * sin).astype(x.dtype), x[..., rot:]], axis=-1)

    return f


@register("flash_attention")
def _flash_attention_op(num_heads=1, causal=False, scale=None):
    def f(q, k, v, *segments):
        # canonical layout (B, H, T, D); rank-2/3 operands (headless
        # attention, e.g. the optimize_for rewrite of a 3-D matmul chain)
        # are lifted to 4-D and the output restored — the kernel itself is
        # rank-4 only. Optional 4th/5th inputs: (B, Tq)/(B, Tk) segment
        # ids (one id given → used for both sides), keeping padded/packed
        # batches on the fused path
        from .pallas_kernels import flash_attention

        ndim = q.ndim
        if ndim == 2:
            qq, kk, vv = (a[None, None] for a in (q, k, v))
        elif ndim == 3:
            qq, kk, vv = (a[:, None] for a in (q, k, v))
        elif ndim == 4:
            qq, kk, vv = q, k, v
        else:
            raise MXNetError(
                f"flash_attention expects rank 2-4 operands, got {ndim}")
        q_seg = k_seg = None
        if segments:
            q_seg = segments[0]
            k_seg = segments[1] if len(segments) > 1 else segments[0]
            if ndim == 2:
                q_seg, k_seg = q_seg[None], k_seg[None]
        out = flash_attention(qq, kk, vv, scale, causal,
                              q_segment_ids=q_seg, kv_segment_ids=k_seg)
        if ndim == 2:
            return out[0, 0]
        if ndim == 3:
            return out[:, 0]
        return out

    return f
