"""Sliding-window attention, DGL graph-sampling, and image/cv operators.

Three reference op families:

- ``_contrib_sldwin_atten_*`` (src/operator/contrib/transformer.cc): banded
  (Longformer-style) attention. TPU-first design: the band is materialized as
  a static-width gather — score/context are dense ``(B, L, H, W)`` einsums
  that XLA tiles straight onto the MXU; per-head dilation arrives as a
  tensor operand exactly like the reference.
- ``_contrib_dgl_*`` + ``_contrib_edge_id``/``_contrib_getnnz``
  (src/operator/contrib/dgl_graph.cc): graph sampling over CSR. The
  reference pins these to CPU (FComputeEx<cpu> only); we keep the same
  contract — eager host-side ops (``jit=False``) over (indptr, indices)
  operands, since data-dependent output shapes cannot trace under jit.
- ``_image_*`` / ``_cv*`` (src/operator/image/*.cc, plugin/opencv): bridges
  onto mxnet_tpu.image's host pipeline (per-sample work stays on host numpy —
  a device round-trip per sample would be a latency disaster).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as onp

from .registry import register, register_alias

# ---------------------------------------------------------------------------
# sliding-window attention — contrib/transformer.cc (sldwin_atten_score,
# sldwin_atten_context, sldwin_atten_mask_like)
# ---------------------------------------------------------------------------
def _band_offsets(w, symmetric):
    # symmetric: [-w..w]; causal: [-w..0] (reference band layout)
    return jnp.arange(-w, w + 1) if symmetric else jnp.arange(-w, 1)


def _band_index(L, H, dilation, w, symmetric):
    """idx[h, l, k] = l + offset_k * dilation_h, clipped to [0, L-1];
    also returns the validity mask of the unclipped index."""
    offs = _band_offsets(w, symmetric)              # (W,)
    d = dilation.astype(jnp.int32).reshape(H, 1, 1)  # (H,1,1)
    pos = jnp.arange(L).reshape(1, L, 1)
    raw = pos + offs.reshape(1, 1, -1) * d           # (H, L, W)
    valid = (raw >= 0) & (raw < L)
    return jnp.clip(raw, 0, L - 1), valid


@register("sldwin_atten_score")
def _sldwin_score(w=1, symmetric=True, **a):
    def f(query, key, dilation):
        B, L, H, D = query.shape
        idx, valid = _band_index(L, H, dilation, w, symmetric)
        k_t = key.transpose(0, 2, 1, 3)              # (B,H,L,D)
        kb = k_t[:, jnp.arange(H)[:, None, None], idx, :]  # (B,H,L,W,D)
        q_t = query.transpose(0, 2, 1, 3)            # (B,H,L,D)
        score = jnp.einsum("bhld,bhlwd->bhlw", q_t, kb)
        score = jnp.where(valid[None], score, 0.0)
        return score.transpose(0, 2, 1, 3)           # (B,L,H,W)

    return f


@register("sldwin_atten_context")
def _sldwin_context(w=1, symmetric=True, **a):
    def f(score, value, dilation):
        B, L, H, W = score.shape
        idx, valid = _band_index(L, H, dilation, w, symmetric)
        v_t = value.transpose(0, 2, 1, 3)            # (B,H,L,D)
        vb = v_t[:, jnp.arange(H)[:, None, None], idx, :]  # (B,H,L,W,D)
        s_t = score.transpose(0, 2, 1, 3)            # (B,H,L,W)
        s_t = jnp.where(valid[None], s_t, 0.0)
        ctx = jnp.einsum("bhlw,bhlwd->bhld", s_t, vb)
        return ctx.transpose(0, 2, 1, 3)             # (B,L,H,D)

    return f


@register("sldwin_atten_mask_like")
def _sldwin_mask_like(w=1, symmetric=True, **a):
    def f(score, dilation, val_length):
        B, L, H, W = score.shape
        idx, valid = _band_index(L, H, dilation, w, symmetric)
        vl = val_length.astype(jnp.int32).reshape(B, 1, 1, 1)
        in_len = idx[None] < vl                       # (B,H,L,W)
        pos_ok = (jnp.arange(L).reshape(1, 1, L, 1) < vl)
        mask = valid[None] & in_len & pos_ok
        return mask.transpose(0, 2, 1, 3).astype(score.dtype)

    return f


for _n in ("score", "context", "mask_like"):
    register_alias(f"_contrib_sldwin_atten_{_n}", f"sldwin_atten_{_n}")

# ---------------------------------------------------------------------------
# DGL graph sampling — contrib/dgl_graph.cc. CSR travels as (indptr, indices)
# int operands. Eager/host-only by contract (CPU-pinned in the reference too).
# ---------------------------------------------------------------------------
@register("dgl_adjacency", jit=False, differentiable=False)
def _dgl_adjacency(**a):
    """Adjacency-like CSR with all-ones data (reference _contrib_dgl_adjacency
    returns the graph's adjacency as a CSR of 1s): dense here."""
    def f(indptr, indices):
        ip = onp.asarray(indptr)
        ix = onp.asarray(indices)
        n = ip.shape[0] - 1
        out = onp.zeros((n, n), dtype="float32")
        for u in range(n):
            out[u, ix[ip[u]:ip[u + 1]]] = 1.0
        return jnp.asarray(out)

    return f


@register("dgl_subgraph", nout=2, jit=False, differentiable=False)
def _dgl_subgraph(return_mapping=False, **a):
    """Vertex-induced subgraph: returns (sub_indptr, sub_indices[, eids])."""
    def f(indptr, indices, vids):
        ip, ix = onp.asarray(indptr), onp.asarray(indices)
        vs = onp.asarray(vids).astype("int32")
        relabel = {int(v): i for i, v in enumerate(vs)}
        new_ip = [0]
        new_ix = []
        eids = []
        for v in vs:
            for e in range(int(ip[v]), int(ip[v + 1])):
                u = int(ix[e])
                if u in relabel:
                    new_ix.append(relabel[u])
                    eids.append(e)
            new_ip.append(len(new_ix))
        outs = (jnp.asarray(onp.asarray(new_ip, "int32")),
                jnp.asarray(onp.asarray(new_ix, "int32")))
        if return_mapping:
            outs = outs + (jnp.asarray(onp.asarray(eids, "int32")),)
        return outs

    return f


@register("dgl_csr_neighbor_uniform_sample", nout=2, jit=False,
          differentiable=False, needs_rng=True)
def _dgl_neighbor_uniform(num_hops=1, num_neighbor=2, max_num_vertices=100,
                          **a):
    """Uniform neighbor sampling from seeds (NodeFlow layer 0): returns
    (sampled_vertices padded to max_num_vertices with -1, layer offsets)."""
    def f(key, indptr, indices, seeds):
        ip, ix = onp.asarray(indptr), onp.asarray(indices)
        rng = onp.random.RandomState(
            int(onp.asarray(jax.random.key_data(key)).ravel()[-1] % 2**31))
        frontier = list(dict.fromkeys(int(s) for s in onp.asarray(seeds)))
        seen = list(frontier)
        seen_set = set(seen)
        offsets = [0, len(frontier)]
        for _ in range(num_hops):
            nxt = []
            for v in frontier:
                nbrs = ix[ip[v]:ip[v + 1]]
                if len(nbrs) == 0:
                    continue
                take = rng.choice(nbrs, size=min(num_neighbor, len(nbrs)),
                                  replace=False)
                nxt.extend(int(u) for u in take)
            nxt = [u for u in dict.fromkeys(nxt) if u not in seen_set]
            seen.extend(nxt)
            seen_set.update(nxt)
            frontier = nxt
            offsets.append(len(seen))
        out = onp.full(max_num_vertices, -1, "int32")
        out[:len(seen)] = seen[:max_num_vertices]
        return (jnp.asarray(out),
                jnp.asarray(onp.asarray(offsets, "int32")))

    return f


@register("dgl_csr_neighbor_non_uniform_sample", nout=2, jit=False,
          differentiable=False, needs_rng=True)
def _dgl_neighbor_non_uniform(num_hops=1, num_neighbor=2,
                              max_num_vertices=100, **a):
    """Importance-weighted neighbor sampling: per-vertex probability array
    is the extra operand (reference non-uniform variant)."""
    def f(key, indptr, indices, probability, seeds):
        ip, ix = onp.asarray(indptr), onp.asarray(indices)
        prob = onp.asarray(probability).astype("float64")
        rng = onp.random.RandomState(
            int(onp.asarray(jax.random.key_data(key)).ravel()[-1] % 2**31))
        frontier = list(dict.fromkeys(int(s) for s in onp.asarray(seeds)))
        seen = list(frontier)
        seen_set = set(seen)
        offsets = [0, len(frontier)]
        for _ in range(num_hops):
            nxt = []
            for v in frontier:
                nbrs = ix[ip[v]:ip[v + 1]]
                if len(nbrs) == 0:
                    continue
                p = prob[nbrs]
                total = p.sum()
                if total <= 0:
                    continue  # no reachable neighbor under this measure
                p = p / total
                # without replacement only as many draws as non-zero-prob
                # neighbors exist
                take = rng.choice(
                    nbrs, size=min(num_neighbor, int((p > 0).sum())),
                    replace=False, p=p)
                nxt.extend(int(u) for u in take)
            nxt = [u for u in dict.fromkeys(nxt) if u not in seen_set]
            seen.extend(nxt)
            seen_set.update(nxt)
            frontier = nxt
            offsets.append(len(seen))
        out = onp.full(max_num_vertices, -1, "int32")
        out[:len(seen)] = seen[:max_num_vertices]
        return (jnp.asarray(out),
                jnp.asarray(onp.asarray(offsets, "int32")))

    return f


@register("dgl_graph_compact", nout=2, jit=False, differentiable=False)
def _dgl_graph_compact(return_mapping=False, graph_sizes=(), **a):
    """Relabel a padded vertex-id graph to a compact [0, n) id space."""
    def f(indptr, indices, vids):
        ip, ix = onp.asarray(indptr), onp.asarray(indices)
        vs = [int(v) for v in onp.asarray(vids) if v >= 0]
        relabel = {v: i for i, v in enumerate(vs)}
        new_ip = [0]
        new_ix = []
        for v in vs:
            row = [relabel[int(u)] for u in ix[ip[v]:ip[v + 1]]
                   if int(u) in relabel]
            new_ix.extend(row)
            new_ip.append(len(new_ix))
        return (jnp.asarray(onp.asarray(new_ip, "int32")),
                jnp.asarray(onp.asarray(new_ix, "int32")))

    return f


@register("edge_id", jit=False, differentiable=False)
def _edge_id(**a):
    """edge_id(csr, u, v) -> data index of edge (u,v), -1 if absent
    (contrib/dgl_graph.cc _contrib_edge_id)."""
    def f(indptr, indices, u, v):
        ip, ix = onp.asarray(indptr), onp.asarray(indices)
        us, vs = onp.asarray(u).ravel(), onp.asarray(v).ravel()
        out = onp.full(us.shape, -1, "int32")
        for i, (a_, b_) in enumerate(zip(us, vs)):
            row = ix[ip[int(a_)]:ip[int(a_) + 1]]
            hit = onp.nonzero(row == int(b_))[0]
            if hit.size:
                out[i] = int(ip[int(a_)]) + int(hit[0])
        return jnp.asarray(out)

    return f


register_alias("_contrib_dgl_adjacency", "dgl_adjacency")
register_alias("_contrib_dgl_subgraph", "dgl_subgraph")
register_alias("_contrib_dgl_csr_neighbor_uniform_sample",
               "dgl_csr_neighbor_uniform_sample")
register_alias("_contrib_dgl_csr_neighbor_non_uniform_sample",
               "dgl_csr_neighbor_non_uniform_sample")
register_alias("_contrib_dgl_graph_compact", "dgl_graph_compact")
register_alias("_contrib_edge_id", "edge_id")

register("getnnz", lambda axis=None, **a:
         (lambda x: jnp.count_nonzero(x, axis=axis).astype(jnp.int32)),
         differentiable=False)
register_alias("_contrib_getnnz", "getnnz")

# ---------------------------------------------------------------------------
# image ops — src/operator/image/{resize,crop,normalize}.cc + plugin/opencv
# (_cvimdecode/_cvimread/_cvimresize/_cvcopyMakeBorder). Host-side bridges
# onto mxnet_tpu.image.
# ---------------------------------------------------------------------------
def _img_mod():
    from .. import image as img

    return img


register("image_to_tensor", lambda **a:
         (lambda x: (x.astype(jnp.float32) / 255.0).transpose(
             (2, 0, 1) if x.ndim == 3 else (0, 3, 1, 2))))
register_alias("_image_to_tensor", "image_to_tensor")

register("image_normalize", lambda mean=0.0, std=1.0, **a:
         (lambda x: (x - jnp.asarray(mean, x.dtype).reshape(-1, 1, 1))
          / jnp.asarray(std, x.dtype).reshape(-1, 1, 1)))
register_alias("_image_normalize", "image_normalize")


@register("image_resize", jit=False, differentiable=False)
def _image_resize(size=(), keep_ratio=False, interp=1, **a):
    def f(x):
        img = _img_mod()
        from ..ndarray.ndarray import NDArray

        if keep_ratio and isinstance(size, int):
            # reference image/resize.cc: int size + keep_ratio resizes the
            # shorter edge and preserves aspect
            return img.resize_short(NDArray(x), size, interp=interp)._data
        h, w = (size, size) if isinstance(size, int) else \
            (size[1], size[0])
        out = img.imresize(NDArray(x), w, h, interp=interp)
        return out._data

    return f


register_alias("_image_resize", "image_resize")


@register("image_crop", jit=False, differentiable=False)
def _image_crop(x=0, y=0, width=0, height=0, **a):
    def f(data):
        return data[y:y + height, x:x + width]

    return f


register_alias("_image_crop", "image_crop")


@register("image_random_crop", jit=False, differentiable=False)
def _image_random_crop(size=(), interp=1, **a):
    # randomness comes from the image pipeline's host rng (seeded by
    # mx.random.seed), matching the rest of the host-side augmenters
    def f(data):
        img = _img_mod()
        from ..ndarray.ndarray import NDArray

        out, _ = img.random_crop(NDArray(data),
                                 size if not isinstance(size, int)
                                 else (size, size), interp=interp)
        return out._data

    return f


register_alias("_image_random_crop", "image_random_crop")


@register("image_random_resized_crop", jit=False, differentiable=False)
def _image_random_resized_crop(size=(), scale=(0.08, 1.0),
                               ratio=(0.75, 1.333), interp=1, **a):
    def f(data):
        img = _img_mod()
        from ..ndarray.ndarray import NDArray

        aug = img.RandomSizedCropAug(
            size if not isinstance(size, int) else (size, size),
            scale, ratio, interp)
        return aug(NDArray(data))._data

    return f


register_alias("_image_random_resized_crop", "image_random_resized_crop")


@register("cvimresize", jit=False, differentiable=False)
def _cvimresize(w=0, h=0, interp=1, **a):
    def f(x):
        img = _img_mod()
        from ..ndarray.ndarray import NDArray

        return img.imresize(NDArray(x), w, h, interp=interp)._data

    return f


register_alias("_cvimresize", "cvimresize")


@register("cvcopyMakeBorder", jit=False, differentiable=False)
def _cv_copy_make_border(top=0, bot=0, left=0, right=0, type=0, value=0.0,
                         **a):
    def f(x):
        return jnp.pad(x, ((top, bot), (left, right)) +
                       ((0, 0),) * (x.ndim - 2),
                       constant_values=value)

    return f


register_alias("_cvcopyMakeBorder", "cvcopyMakeBorder")


@register("cvimdecode", jit=False, differentiable=False)
def _cvimdecode(flag=1, to_rgb=True, **a):
    def f(buf):
        img = _img_mod()
        raw = onp.asarray(buf).astype("uint8").tobytes()
        return img.imdecode(raw, flag=flag, to_rgb=to_rgb)._data

    return f


register_alias("_cvimdecode", "cvimdecode")


@register("cvimread", jit=False, differentiable=False)
def _cvimread(filename="", flag=1, to_rgb=True, **a):
    def f():
        img = _img_mod()
        return img.imread(filename, flag=flag, to_rgb=to_rgb)._data

    return f


register_alias("_cvimread", "cvimread")
