"""mx.npx — numpy_extension: NN operators and framework controls.

Reference: python/mxnet/numpy_extension (npx namespace: nn ops from
src/operator/nn/*, sequence ops, control flow, waitall/engine controls).
"""
from __future__ import annotations

from ..base import MXNetError, dtype_name as _dtype_name
from ..ndarray.ndarray import NDArray
from ..ops.registry import apply_op as _op
from .. import autograd as _ag
from .. import engine as _engine
from ..context import cpu, gpu, tpu, num_gpus, num_tpus, current_context  # noqa: F401

_np_active = True


def set_np(shape=True, array=True, dtype=False):
    """Reference parity: numpy semantics are always on in this framework."""
    return True


def reset_np():
    return True


def is_np_array():
    return True


def is_np_shape():
    return True


def use_np(func):
    return func


use_np_array = use_np


def waitall():
    _engine.wait_all()


def _nd(x):
    return x if isinstance(x, NDArray) else NDArray(x)


# -- NN ops ------------------------------------------------------------------
def fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False,
                    flatten=True):
    args = [_nd(data), _nd(weight)]
    if bias is not None and not no_bias:
        args.append(_nd(bias))
        no_bias_eff = False
    else:
        no_bias_eff = True
    return _op("fully_connected", *args, no_bias=no_bias_eff, flatten=flatten,
               num_hidden=num_hidden)


def convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=0, num_group=1, no_bias=False, layout=None,
                **kw):
    args = [_nd(data), _nd(weight)]
    no_bias_eff = bias is None or no_bias
    if not no_bias_eff:
        args.append(_nd(bias))
    return _op("convolution", *args, kernel=tuple(kernel),
               stride=tuple(stride), dilate=tuple(dilate), pad=tuple(pad),
               num_filter=num_filter, num_group=num_group,
               no_bias=no_bias_eff, layout=layout)


def deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), num_filter=0, num_group=1, no_bias=False,
                  layout=None, **kw):
    args = [_nd(data), _nd(weight)]
    no_bias_eff = bias is None or no_bias
    if not no_bias_eff:
        args.append(_nd(bias))
    return _op("deconvolution", *args, kernel=tuple(kernel),
               stride=tuple(stride), dilate=tuple(dilate), pad=tuple(pad),
               adj=tuple(adj), num_filter=num_filter, num_group=num_group,
               no_bias=no_bias_eff, layout=layout)


def pooling(data, kernel=(), pool_type="max", stride=(), pad=(),
            global_pool=False, count_include_pad=True, layout=None,
            ceil_mode=False, **kw):
    return _op("pooling", _nd(data), kernel=tuple(kernel),
               pool_type=pool_type, stride=tuple(stride), pad=tuple(pad),
               global_pool=global_pool, count_include_pad=count_include_pad,
               layout=layout, ceil_mode=ceil_mode)


def batch_norm(x, gamma, beta, running_mean, running_var, eps=1e-5,
               momentum=0.9, fix_gamma=False, use_global_stats=False,
               output_mean_var=False, axis=1):
    use_batch = _ag.is_training() and not use_global_stats
    out, new_mean, new_var = _op(
        "batch_norm", _nd(x), _nd(gamma), _nd(beta), _nd(running_mean),
        _nd(running_var), eps=eps, momentum=momentum, fix_gamma=fix_gamma,
        use_batch_stats=use_batch, axis=axis)
    return out, new_mean, new_var


def layer_norm(data, gamma, beta, axis=-1, eps=1e-5):
    return _op("layer_norm", _nd(data), _nd(gamma), _nd(beta), axis=axis,
               eps=eps)


def group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    return _op("group_norm", _nd(data), _nd(gamma), _nd(beta),
               num_groups=num_groups, eps=eps)


def instance_norm(data, gamma, beta, eps=1e-5):
    return _op("instance_norm", _nd(data), _nd(gamma), _nd(beta), eps=eps)


def rms_norm(data, gamma, axis=-1, eps=1e-6):
    return _op("rms_norm", _nd(data), _nd(gamma), axis=axis, eps=eps)


def activation(data, act_type="relu"):
    return _op("activation", _nd(data), act_type=act_type)


def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, **kw):
    if act_type == "prelu":
        return _op("leaky_relu", _nd(data), _nd(gamma), act_type=act_type)
    return _op("leaky_relu", _nd(data), act_type=act_type, slope=slope)


def relu(data):
    return _op("relu", _nd(data))


def sigmoid(data):
    return _op("sigmoid", _nd(data))


def softmax(data, axis=-1, length=None, temperature=None, use_length=False):
    if length is not None:
        return _op("softmax", _nd(data), _nd(length), axis=axis,
                   temperature=temperature, use_length=True)
    return _op("softmax", _nd(data), axis=axis, temperature=temperature)


def log_softmax(data, axis=-1, temperature=None):
    return _op("log_softmax", _nd(data), axis=axis, temperature=temperature)


def masked_softmax(data, mask, axis=-1, temperature=1.0):
    return _op("masked_softmax", _nd(data), _nd(mask), axis=axis,
               temperature=temperature)


def dropout(data, p=0.5, mode="training", **kw):
    return _op("dropout", _nd(data), p=p, mode=mode,
               training=_ag.is_training() or mode == "always")


def embedding(data, weight, input_dim=0, output_dim=0, dtype="float32",
              sparse_grad=False):
    return _op("embedding", _nd(data), _nd(weight), input_dim=input_dim,
               output_dim=output_dim, sparse_grad=sparse_grad)


def one_hot(data, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    return _op("one_hot", _nd(data), depth=depth, on_value=on_value,
               off_value=off_value, dtype=dtype)


def pick(data, index, axis=-1, mode="clip", keepdims=False):
    return _op("pick", _nd(data), _nd(index), axis=axis, mode=mode,
               keepdims=keepdims)


def topk(data, k=1, axis=-1, ret_typ="indices", is_ascend=False):
    return _op("topk", _nd(data), k=k, axis=axis, ret_typ=ret_typ,
               is_ascend=is_ascend)


def smooth_l1(data, scalar=1.0):
    return _op("smooth_l1", _nd(data), scalar=scalar)


def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             blank_label="first"):
    args = [_nd(data), _nd(label)]
    if data_lengths is not None:
        args.append(_nd(data_lengths))
    if label_lengths is not None:
        args.append(_nd(label_lengths))
    return _op("ctc_loss", *args, use_data_lengths=data_lengths is not None,
               use_label_lengths=label_lengths is not None,
               blank_label=blank_label)


def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    if sequence_length is not None:
        return _op("sequence_mask", _nd(data), _nd(sequence_length),
                   use_sequence_length=True, value=value, axis=axis)
    return _op("sequence_mask", _nd(data), use_sequence_length=False,
               value=value, axis=axis)


def sequence_reverse(data, sequence_length=None, use_sequence_length=False,
                     axis=0):
    if sequence_length is not None:
        return _op("sequence_reverse", _nd(data), _nd(sequence_length),
                   use_sequence_length=True, axis=axis)
    return _op("sequence_reverse", _nd(data), use_sequence_length=False,
               axis=axis)


def sequence_last(data, sequence_length=None, use_sequence_length=False,
                  axis=0):
    if sequence_length is not None:
        return _op("sequence_last", _nd(data), _nd(sequence_length),
                   use_sequence_length=True, axis=axis)
    return _op("sequence_last", _nd(data), use_sequence_length=False,
               axis=axis)


def flash_attention(query, key, value, causal=False, scale=None):
    """Fused online-softmax attention over (B, H, T, D) operands (Pallas on
    TPU). TPU-native extension; see ops/pallas_kernels.py."""
    return _op("flash_attention", _nd(query), _nd(key), _nd(value),
               causal=causal, scale=scale)


def multihead_attention(query, key, value, mask=None, num_heads=1,
                        dropout=0.0, causal=False, scale=None,
                        num_kv_heads=None):
    """``num_kv_heads`` enables grouped-query / multi-query attention:
    key/value carry that many heads, each shared by a group of query
    heads (TPU-native extension beyond the reference).

    Masking note: a (B, 1, 1, Tk) key-padding mask rides the fused flash
    path via segment ids. For the degenerate case of a fully-masked query
    row the fused path emits zeros, whereas the dense where-mask branch
    (any other mask shape) yields a ~uniform softmax over -inf logits.
    Rows with at least one valid key are identical on both paths. The
    same applies to graphs rewritten by ``optimize_for("tpu")``'s
    attention-fusion pass."""
    args = [_nd(query), _nd(key), _nd(value)]
    if mask is not None:
        args.append(_nd(mask))
    return _op("multihead_attention", *args, num_heads=num_heads,
               dropout=dropout, causal=causal, scale=scale,
               num_kv_heads=num_kv_heads)


def paged_decode_attention(query, k_pool, v_pool, layer, page_table,
                           positions, scale=None, k=None, v=None):
    """Decode attention read straight from a paged KV pool: ``query``
    (S, K, Hq, D) — query k of slot s at ``positions[s] + k`` — against
    the pages ``page_table`` (S, W+1) maps in layer ``layer`` (an int32
    scalar array) of the pools [pages, layers, Hkv, D, page_tokens].
    Returns (S, K, Hq*D); an unmapped page contributes nothing, a slot
    with none returns zeros. Given ``k``, ``v`` (S, 1, Hkv, D), the new
    rows of a K = 1 tick, it stores each at its slot's position first (a
    slot whose page there is unmapped stores nothing) and returns ``(out,
    k_pool, v_pool)``. TPU-native extension; see ops/pallas_kernels.py."""
    rows = [] if k is None else [_nd(k), _nd(v)]
    return _op("paged_decode_attention", _nd(query), _nd(k_pool),
               _nd(v_pool), _nd(layer), _nd(page_table), _nd(positions),
               *rows, scale=scale)


def mla_decode_attention(query, pool, layer, page_table, positions,
                         value_dim, scale):
    """Absorbed multi-head latent attention read straight from a latent
    pool: ``query`` (S, K, H, R), each head's ``[W_UK q_nope | q_rope]`` —
    query k of slot s at ``positions[s] + k`` — against the ONE row a
    position ``[c (value_dim) | k_rope]`` that the pages ``page_table`` (S,
    W+1) maps in layer ``layer`` of the pool [pages, layers, 1, R,
    page_tokens] hold; the values are the row's first ``value_dim`` columns.
    Returns (S, K, H*value_dim), still latent: the caller applies ``W_UV``.
    TPU-native extension; see ops/pallas_kernels.py."""
    return _op("mla_decode_attention", _nd(query), _nd(pool), _nd(layer),
               _nd(page_table), _nd(positions), value_dim=int(value_dim),
               scale=float(scale))


def rope(data, rotary_dim=None, theta=10000.0, offset=0, positions=None,
         inv_freq=None):
    """Rotary position embedding (rotate-half) on the first ``rotary_dim``
    entries of the last axis of ``data`` (B, T, H, D). Position t of every
    row is ``offset + t``; ``positions`` (B or 1, T) int32 gives them A ROW
    instead (a decode tick's slots stand at different positions).
    ``inv_freq``: the ``rotary_dim / 2`` inverse frequencies as a sequence
    of floats, for schemes that are not ``theta ** (-i / half)`` (YaRN).
    Angles, cos, sin and the rotation are float32. TPU-native extension."""
    args = [_nd(data)] + ([] if positions is None else [_nd(positions)])
    return _op("rope", *args, rotary_dim=rotary_dim, theta=theta,
               offset=offset, inv_freq=None if inv_freq is None
               else tuple(float(v) for v in inv_freq))


def causal_conv1d(data, weight, activation=None):
    """Depthwise causal convolution over time: ``data`` (B, T, C), ``weight``
    (C, K), zeros left of the sequence, no bias; ``activation`` None or
    "silu". TPU-native extension; see ops/delta_rule.py."""
    return _op("causal_conv1d", _nd(data), _nd(weight),
               activation=activation)


def causal_conv1d_state(data, weight, bias, tail=None, valid_length=None,
                        activation=None):
    """``causal_conv1d`` with a bias and a carried tail: ``tail`` (B, K - 1,
    C) stands left of ``data`` (B, T, C) (default: zeros), and the second
    result is the new tail, the last K - 1 inputs before ``valid_length``
    (B,) (default: T). TPU-native extension; see ops/ssm.py."""
    from .. import numpy as _np

    data = _nd(data)
    B, T, C = data.shape
    if tail is None:
        tail = _np.zeros((B, weight.shape[1] - 1, C),
                         dtype=_dtype_name(data.dtype))
    if valid_length is None:
        valid_length = _np.full((B,), T, dtype="int32")
    return _op("causal_conv1d_state", data, _nd(weight), _nd(bias),
               _nd(tail), _nd(valid_length), activation=activation)


def ssd_chunk_scan(x, dt, A, B, C, D, state=None, valid_length=None,
                   chunk=256):
    """The Mamba-2 recurrence ``S = exp(dt_t A) S + dt_t x_t B_t^T; y_t = S
    C_t + D x_t`` over ``x`` (B, T, H, P) in chunks, from ``state`` (B, H, P,
    N) (default: zeros); positions at or past ``valid_length`` (B,) leave
    the state alone. Returns ``(y, final state)``. TPU-native extension; see
    ops/ssm.py."""
    from .. import numpy as _np

    x = _nd(x)
    Bsz, T, H, P = x.shape
    if state is None:
        state = _np.zeros((Bsz, H, P, B.shape[-1]), dtype="float32")
    if valid_length is None:
        valid_length = _np.full((Bsz,), T, dtype="int32")
    return _op("ssd_chunk_scan", x, _nd(dt), _nd(A), _nd(B), _nd(C), _nd(D),
               _nd(state), _nd(valid_length), chunk=chunk)


def ssd_step(x, dt, A, B, C, D, state):
    """One step of the Mamba-2 recurrence for every row: ``x`` (S, H, P),
    ``state`` (S, H, P, N). Returns ``(y, new state)``. TPU-native
    extension; see ops/ssm.py."""
    return _op("ssd_step", _nd(x), _nd(dt), _nd(A), _nd(B), _nd(C), _nd(D),
               _nd(state))


def gated_delta_rule(query, key, value, g, beta, chunk=64, scale=None,
                     l2norm=True):
    """Linear attention by the gated delta rule, computed in chunks:
    ``S = exp(g_t) S; S += k_t (beta_t (v_t - S^T k_t))^T; o_t = S^T q_t``
    per value head. ``query``/``key``: (B, T, H_k, d_k); ``value``:
    (B, T, H_v, d_v); ``g`` (log-decay) and ``beta``: (B, T, H_v).
    TPU-native extension; see ops/delta_rule.py."""
    return _op("gated_delta_rule", _nd(query), _nd(key), _nd(value), _nd(g),
               _nd(beta), chunk=chunk, scale=scale, l2norm=l2norm)


def moe_router(data, weight, top_k=1, norm_topk=True, score="softmax",
               scaling=1.0):
    """``softmax(data weight^T)`` in float32 over the router's full width,
    its ``top_k`` largest renormalised: ``(weights (N, k), experts (N, k)
    int32, counts (E,))``. ``score="sigmoid"``: ``sigmoid`` an expert in
    place of the softmax, the chosen ones over their sum + 1e-20, times
    ``scaling``. TPU-native extension; see ops/moe.py."""
    return _op("moe_router", _nd(data), _nd(weight), top_k=top_k,
               norm_topk=norm_topk, score=score, scaling=float(scaling))


def routed_experts(data, weights, experts, gate_up, down, experts_held=None,
                   tile=128):
    """Dropless routed SwiGLU experts of a layer that holds the experts
    ``experts_held = (lo, hi)`` of the router's width: the weighted sum over
    each token's chosen experts that are held. TPU-native extension; see
    ops/moe.py."""
    return _op("routed_experts", _nd(data), _nd(weights), _nd(experts),
               _nd(gate_up), _nd(down),
               experts_held=None if experts_held is None
               else tuple(int(e) for e in experts_held), tile=tile)


def adaptive_avg_pool2d(data, output_size=1):
    return _op("adaptive_avg_pool2d", _nd(data), output_size=output_size)


def arange_like(data, start=0.0, step=1.0, axis=None):
    import jax.numpy as jnp

    d = _nd(data)
    n = d.size if axis is None else d.shape[axis]
    return NDArray(jnp.arange(n) * step + start)


def gamma(data):
    return _op("gamma", _nd(data))


def gammaln(data):
    return _op("gammaln", _nd(data))


def erf(data):
    return _op("erf", _nd(data))


def erfinv(data):
    return _op("erfinv", _nd(data))


def stop_gradient(data):
    return _op("stop_gradient", _nd(data))


def cast(data, dtype):
    return _nd(data).astype(dtype)


def reshape_like(lhs, rhs):
    return _nd(lhs).reshape(_nd(rhs).shape)


def broadcast_like(lhs, rhs):
    return _nd(lhs).broadcast_to(_nd(rhs).shape)


def slice_axis(data, axis=0, begin=0, end=None):
    key = [slice(None)] * _nd(data).ndim
    key[axis] = slice(begin, end)
    return _nd(data)[tuple(key)]


def slice_like(data, shape_like, axes=None):
    d, s = _nd(data), _nd(shape_like)
    key = []
    for i in range(d.ndim):
        if axes is None or i in axes:
            key.append(slice(0, s.shape[i]))
        else:
            key.append(slice(None))
    return d[tuple(key)]


def custom(*inputs, op_type, **kwargs):
    """Invoke a registered python custom op (reference: npx.custom /
    nd.Custom over src/operator/custom)."""
    from ..operator import custom as _custom

    return _custom(*[_nd(x) for x in inputs], op_type=op_type, **kwargs)


# control flow lowered to lax.scan/while/cond lives in .control_flow
from .control_flow import foreach, while_loop, cond  # noqa: E402,F401


# -- detection / vision ops (ops/vision.py; reference contrib/bounding_box.cc,
#    roi_pooling.cc, roi_align.cc, nn/upsampling.cc, bilinear_resize.cc) -----
def box_iou(lhs, rhs, format="corner"):  # noqa: A002
    return _op("box_iou", _nd(lhs), _nd(rhs), format=format)


def box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1,
            coord_start=2, score_index=1, id_index=-1, background_id=-1,
            force_suppress=False, in_format="corner", out_format="corner"):
    return _op("box_nms", _nd(data), overlap_thresh=overlap_thresh,
               valid_thresh=valid_thresh, topk=topk, coord_start=coord_start,
               score_index=score_index, id_index=id_index,
               background_id=background_id, force_suppress=force_suppress,
               in_format=in_format, out_format=out_format)


def box_encode(samples, matches, anchors, refs,
               means=(0.0, 0.0, 0.0, 0.0), stds=(0.1, 0.1, 0.2, 0.2)):
    return _op("box_encode", _nd(samples), _nd(matches), _nd(anchors),
               _nd(refs), means=tuple(means), stds=tuple(stds))


def box_decode(data, anchors, std0=0.1, std1=0.1, std2=0.2, std3=0.2,
               clip=-1.0, format="center"):  # noqa: A002
    return _op("box_decode", _nd(data), _nd(anchors), std0=std0, std1=std1,
               std2=std2, std3=std3, clip=clip, format=format)


def roi_pooling(data, rois, pooled_size=(7, 7), spatial_scale=1.0):
    return _op("roi_pooling", _nd(data), _nd(rois),
               pooled_size=tuple(pooled_size), spatial_scale=spatial_scale)


def roi_align(data, rois, pooled_size=(7, 7), spatial_scale=1.0,
              sample_ratio=2, aligned=False):
    return _op("roi_align", _nd(data), _nd(rois),
               pooled_size=tuple(pooled_size), spatial_scale=spatial_scale,
               sample_ratio=sample_ratio, aligned=aligned)


def upsampling(data, scale=2, sample_type="nearest"):
    return _op("upsampling", _nd(data), scale=scale, sample_type=sample_type)


def bilinear_resize_2d(data, height=0, width=0, scale_height=None,
                       scale_width=None, align_corners=True):
    return _op("bilinear_resize_2d", _nd(data), height=height, width=width,
               scale_height=scale_height, scale_width=scale_width,
               align_corners=align_corners)


def moments(data, axes=None, keepdims=False):
    return _op("moments", _nd(data),
               axes=tuple(axes) if axes is not None else None,
               keepdims=keepdims)


# -- lazily resolve any remaining registered op (generated-wrapper parity) --
def __getattr__(name):
    from ..ops.registry import _OPS, apply_op

    if name not in _OPS:
        raise AttributeError(f"module 'mxnet_tpu.numpy_extension' has no "
                             f"attribute {name!r}")

    def wrapper(*inputs, **attrs):
        out = attrs.pop("out", None)
        arrs = [_nd(x) if hasattr(x, "shape") or isinstance(x, (list, tuple))
                else x for x in inputs]
        return apply_op(name, *arrs, out=out, **attrs)

    wrapper.__name__ = name
    globals()[name] = wrapper
    return wrapper
