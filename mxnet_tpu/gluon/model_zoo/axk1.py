"""A.X-K1: a decoder with multi-head latent attention, YaRN rotary positions, a
leading dense layer and sigmoid-routed experts plus a shared expert
(model-zoo LM family; the keys are DeepSeek-V3's one for one).

Source: https://huggingface.co/skt/A.X-K1/blob/main/config.json
(``model_type`` ``axk1``). With ``norm(x) = x / sqrt(mean(x^2) + eps) * w``::

    h = E[tokens]
    h = h + attn(norm_1(h));  m = norm_2(h)
    h = h + mlp(m)                      (the first ``first_k_dense_replace`` layers)
    h = h + moe(m) + shared(m)          (every other layer)
    logits = norm_f(h) W_head^T         (untied)

**Latent attention** (H heads; a head's query and key are ``d_n`` numbers
without a position signal and ``d_r`` rotary ones, its value ``d_v``)::

    c_q = norm_q(x W_DQ);  [q_h^n | q_h^r] = (c_q W_UQ)_h;  q_h^r = rope(q_h^r)
    [c' | k^r'] = x W_DKV;  c = norm_kv(c');  k^r = rope(k^r')   (ONE for all heads)
    k_h = [W_UK,h c | k^r];  v_h = W_UV,h c
    out = concat_h(softmax_causal(s q_h . k_h) v_h) W_O

``s = (d_n + d_r)^(-1/2) m^2`` with YaRN's ``m = 0.1 mscale_all_dim
ln(factor) + 1``. What a cache must hold a token and layer is the row ``[c |
k^r]`` (``kv_lora_rank + qk_rope_head_dim`` numbers), not per-head K and V.

There is ONE forward pass. Plain, and over a prefill view, attention is
EXPANDED (``k_h``, ``v_h`` as above, the flash path; ``v`` zero-padded to the
key's width, which the flash kernel wants equal). Over a tick view
(``cache.decoding``) it is ABSORBED, the same numbers in another order::

    q~_h = W_UK,h^T q_h^n;  score = s (q~_h . c + q_h^r . k^r)
    u_h = sum_t p_t c_t;    out_h = W_UV,h u_h

so that every head reads the ONE row a position the pages hold
(``cache.attend_latent``, ``npx.mla_decode_attention``). ``cache_spec()``
states the latent row and the routed layers' two counters.

**YaRN** (``yarn_inv_freq``): the ``d_r / 2`` inverse frequencies are a blend
of ``theta^(-2i/d_r)`` and the same over ``factor``, by a ramp between the
dimensions that turn ``beta_fast`` and ``beta_slow`` times over the original
context; they apply at every position. Angles, cos and sin are float32
(``npx.rope``), positions come a ROW from the cache view.

**Routed experts**: ``parallel.moe.RoutedExperts`` with a sigmoid router
(``score = sigmoid(m W_r^T)``, the ``num_experts_per_tok`` largest over ALL
``n_routed_experts``, each over their sum + 1e-20, times
``routed_scaling_factor``) plus a shared expert of ``n_shared_experts x
moe_intermediate_size``, added ungated.

The chip's share of an expert-parallel deployment is part of the model's
arguments, as in ``granite_hybrid``: ``experts_held = (lo, hi)`` of the
``n_routed_experts`` the router keeps, ``vocab_size`` the rows of embedding
and head held, ``num_hidden_layers`` the layers held (from layer 0).

Departures from the published code, shared with the plain reference
(``benchmark/chip/chipbench/reference_axk1.py``): ``kv_b_proj`` is two arrays
(``k_up``, ``v_up``: the absorbed form takes them apart anyway); an expert's
gate and up matrices are one array ``[W_g | W_u]``, as are the shared
expert's and the dense MLP's; the rotary columns are taken as stored in
rotate-half order (a fixed permutation of ``W_UQ``'s and ``W_DKV``'s rotary
columns); ``topk_method`` ``"none"`` is read as a plain top-k (``n_group``,
``topk_group`` unused, no correction bias); no multi-token-prediction
module, no auxiliary loss.

The parts of a layer run under ``AttrScope(__scope__=...)`` names ``attn``,
``mlp``, ``router`` and ``experts``.
"""
from __future__ import annotations

import math

from ... import numpy_extension as npx
from ...attribute import AttrScope
from ...base import MXNetError
from .. import nn
from ..block import HybridBlock
from .granite_hybrid import RMSNorm, RoutedPlusShared, _dense, _NormalAs, \
    _silu

__all__ = ["AXK1Model", "axk1", "axk1_tiny", "AX_K1", "yarn_inv_freq",
           "yarn_mscale"]

# the published config.json's keys that shape the model
AX_K1 = {
    "vocab_size": 163840, "hidden_size": 7168, "num_hidden_layers": 61,
    "intermediate_size": 18432, "first_k_dense_replace": 1,
    "num_attention_heads": 64, "q_lora_rank": 1536, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "n_routed_experts": 192, "num_experts_per_tok": 8,
    "moe_intermediate_size": 2048, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 32,
                     "original_max_position_embeddings": 4096,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                     "mscale_all_dim": 1},
    "max_position_embeddings": 131072,
}


def yarn_inv_freq(dim, theta, scaling):
    """The ``dim / 2`` inverse frequencies of YaRN (``scaling``: the
    config's ``rope_scaling``), as floats: ``f_i = theta^(-2i/dim)``;
    ``corr(b) = dim ln(original / (2 pi b)) / (2 ln theta)``, ``low =
    floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))`` (clipped to
    the dimensions there are); ``ramp_i = clip((i - low) / (high - low), 0,
    1)``; ``inv_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i``. ``scaling``
    None: the plain ``f_i``."""
    half = dim // 2
    f = [float(theta) ** (-2.0 * i / dim) for i in range(half)]
    if scaling is None:
        return f
    if scaling.get("type", "yarn") != "yarn":
        raise MXNetError(f"rope_scaling type {scaling.get('type')!r}: only "
                         "'yarn' is known")
    original = scaling["original_max_position_embeddings"]

    def corr(turns):
        return dim * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(scaling["beta_fast"])), 0)
    high = min(math.ceil(corr(scaling["beta_slow"])), dim - 1)
    span = (high - low) or 0.001
    ramp = [min(max((i - low) / span, 0.0), 1.0) for i in range(half)]
    return [fi * (1 - r) + fi / scaling["factor"] * r
            for fi, r in zip(f, ramp)]


def yarn_mscale(scaling):
    """``(m, ratio)``: the softmax scale's ``m = 0.1 mscale_all_dim
    ln(factor) + 1`` (it enters squared) and what cos and sin are
    multiplied by, ``m(mscale) / m(mscale_all_dim)``."""
    if scaling is None:
        return 1.0, 1.0

    def m(scale):
        if scaling["factor"] <= 1 or not scale:
            return 1.0
        return 0.1 * scale * math.log(scaling["factor"]) + 1.0

    all_dim = m(scaling.get("mscale_all_dim", 0))
    return all_dim, m(scaling.get("mscale", 1)) / all_dim


def _swiglu(x, w_in, w_out, width):
    up = w_in(x)
    return w_out(_silu(npx.slice_axis(up, axis=-1, begin=0, end=width))
                 * npx.slice_axis(up, axis=-1, begin=width, end=None))


class LatentAttention(HybridBlock):
    """Multi-head latent attention. ``kv_index``: which of the model's
    layers this is (its rows' place in a cache's pool)."""

    def __init__(self, cfg, kv_index=0, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        units, H = cfg["hidden_size"], cfg["num_attention_heads"]
        self._h = H
        self._dn, self._dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        self._dv, self._rkv = cfg["v_head_dim"], cfg["kv_lora_rank"]
        if self._dv > self._dn + self._dr:
            raise MXNetError("LatentAttention: a value wider than the key "
                             "is not built (v is padded to the key's width)")
        self._kv_index = kv_index
        scaling = cfg.get("rope_scaling")
        self._inv_freq = tuple(yarn_inv_freq(self._dr, cfg["rope_theta"],
                                             scaling))
        m, ratio = yarn_mscale(scaling)
        if ratio != 1.0:
            raise MXNetError("LatentAttention: mscale != mscale_all_dim "
                             "(cos and sin scaled) is not built")
        self.scale = (self._dn + self._dr) ** -0.5 * m * m
        eps = cfg["rms_norm_eps"]
        self.q_down = _dense(cfg["q_lora_rank"], units, dtype)
        self.q_norm = RMSNorm(cfg["q_lora_rank"], eps, dtype)
        self.q_up = _dense(H * (self._dn + self._dr), cfg["q_lora_rank"],
                           dtype)
        self.kv_down = _dense(self._rkv + self._dr, units, dtype)
        self.kv_norm = RMSNorm(self._rkv, eps, dtype)
        self.k_up = _dense(H * self._dn, self._rkv, dtype)
        self.v_up = _dense(H * self._dv, self._rkv, dtype)
        self.o_proj = _dense(units, H * self._dv, dtype)

    def latent_row(self):
        """(row width, value width) of what a cache holds a position."""
        return (self._rkv + self._dr, self._rkv)

    def _rope(self, x, positions):
        return npx.rope(x, positions=positions, inv_freq=self._inv_freq)

    def forward(self, x, valid_length=None, cache=None, positions=None):
        from ... import numpy as np

        T = x.shape[1]
        H, dn, dr, dv, rkv = self._h, self._dn, self._dr, self._dv, self._rkv
        # -1 for the batch: one traced graph serves every batch bucket
        q = np.reshape(self.q_up(self.q_norm(self.q_down(x))),
                       (-1, T, H, dn + dr))
        q_n = npx.slice_axis(q, axis=-1, begin=0, end=dn)
        q_r = self._rope(npx.slice_axis(q, axis=-1, begin=dn, end=None),
                         positions)
        ckv = self.kv_down(x)
        c = self.kv_norm(npx.slice_axis(ckv, axis=-1, begin=0, end=rkv))
        k_r = self._rope(np.reshape(
            npx.slice_axis(ckv, axis=-1, begin=rkv, end=None),
            (-1, T, 1, dr)), positions)
        row = np.concatenate([c, np.reshape(k_r, (-1, T, dr))], axis=-1)

        if cache is not None and cache.decoding:
            # absorbed: every head against the one row a position
            w_uk = np.reshape(self.k_up.weight.data(), (H, dn, rkv))
            q_abs = np.concatenate(
                [np.einsum("skhn,hnc->skhc", q_n, w_uk), q_r], axis=-1)
            u = cache.attend_latent(
                self._kv_index, row, np.reshape(q_abs, (-1, T, H * (rkv + dr))),
                scale=self.scale, heads=H)
            w_uv = np.reshape(self.v_up.weight.data(), (H, dv, rkv))
            attn = np.reshape(
                np.einsum("skhc,hvc->skhv", np.reshape(u, (-1, T, H, rkv)),
                          w_uv), (-1, T, H * dv))
            return self.o_proj(attn)

        # expanded: per-head keys and values from the row's latent part
        k = np.concatenate([np.reshape(self.k_up(c), (-1, T, H, dn)),
                            np.repeat(k_r, H, axis=2)], axis=-1)
        v = np.reshape(self.v_up(c), (-1, T, H, dv))
        pad = dn + dr - dv
        if pad:
            v = np.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
        flat = (-1, T, H * (dn + dr))
        q = np.reshape(np.concatenate([q_n, q_r], axis=-1), flat)
        k, v = np.reshape(k, flat), np.reshape(v, flat)
        if cache is not None:
            attn = cache.attend_latent(self._kv_index, row, q, k, v,
                                       scale=self.scale, heads=H)
        else:
            mask = None
            if valid_length is not None:
                mask = (np.arange(T, dtype="int32").reshape(1, T)
                        < valid_length.astype("int32").reshape(-1, 1)) \
                    .reshape(-1, 1, 1, T)
            attn = npx.multihead_attention(q, k, v, mask=mask, num_heads=H,
                                           causal=True, scale=self.scale)
        if pad:
            attn = npx.slice_axis(np.reshape(attn, (-1, T, H, dn + dr)),
                                  axis=-1, begin=0, end=dv)
        return self.o_proj(np.reshape(attn, (-1, T, H * dv)))


class DenseMLP(HybridBlock):
    """``(silu(x W_g) * (x W_u)) W_d`` with ``[W_g | W_u]`` one matrix."""

    def __init__(self, units, width, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._width = width
        self.gate_up = _dense(2 * width, units, dtype)
        self.down = _dense(units, width, dtype)

    def forward(self, x):
        return _swiglu(x, self.gate_up, self.down, self._width)


class AXK1Layer(HybridBlock):
    def __init__(self, cfg, index, experts_held, dtype, **kwargs):
        super().__init__(**kwargs)
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.dense = index < cfg["first_k_dense_replace"]
        self.norm_1 = RMSNorm(units, eps, dtype)
        self.attn = LatentAttention(cfg, kv_index=index, dtype=dtype)
        self.norm_2 = RMSNorm(units, eps, dtype)
        if self.dense:
            self.mlp = DenseMLP(units, cfg["intermediate_size"], dtype)
        else:
            self.moe = RoutedPlusShared(
                units, cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                cfg["num_experts_per_tok"],
                cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
                experts_held=experts_held, dtype=dtype,
                norm_topk=cfg["norm_topk_prob"], score=cfg["scoring_func"],
                scaling=cfg["routed_scaling_factor"])

    def forward(self, x, valid_length=None, cache=None, positions=None):
        with AttrScope(__scope__="attn"):
            x = x + self.attn(self.norm_1(x), valid_length=valid_length,
                              cache=cache, positions=positions)
        if self.dense:
            with AttrScope(__scope__="mlp"):
                return x + self.mlp(self.norm_2(x))
        return x + self.moe(self.norm_2(x), cache=cache)


class AXK1Model(HybridBlock):
    """Embedding -> layers (the first ``first_k_dense_replace`` with a dense
    MLP, the others routed + shared experts) -> RMSNorm -> untied head.
    ``config``: the published keys (``AX_K1``) with ``num_hidden_layers``
    and ``vocab_size`` as HELD here; ``n_routed_experts`` stays the router's
    width and ``experts_held`` (default: all) says which are held."""

    def __init__(self, config, experts_held=None, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        missing = sorted(set(AX_K1) - set(config))
        if missing:
            raise MXNetError(f"AXK1Model: the configuration lacks {missing}")
        self.config = {k: config[k] for k in AX_K1}
        cfg = self.config
        self.experts_held = tuple(experts_held) if experts_held is not None \
            else (0, cfg["n_routed_experts"])
        self.vocab_size = cfg["vocab_size"]
        self.max_length = cfg["max_position_embeddings"]
        self._dtype = dtype
        units = cfg["hidden_size"]
        self.embed = nn.Embedding(cfg["vocab_size"], units, dtype=dtype,
                                  weight_initializer=_NormalAs(0.02))
        self.layers = nn.HybridSequential()
        for i in range(cfg["num_hidden_layers"]):
            self.layers.add(AXK1Layer(cfg, i, self.experts_held, dtype))
        self.norm_f = RMSNorm(units, cfg["rms_norm_eps"], dtype)
        self.head = _dense(cfg["vocab_size"], units, dtype)

    def cache_spec(self):
        """What a cache must hold for this model: ONE latent row a position
        and layer, ``(row width, value width)`` (no V pool), and the routed
        layers' counters."""
        return {"layers": len(self.layers),
                "latent": self.layers[0].attn.latent_row(),
                "dtype": self._dtype,
                "counters": ("moe_pairs_here", "moe_experts_touched")}

    def forward(self, tokens, valid_length=None, cache=None):
        """(B, T) token ids of the vocabulary held -> (B, T, vocab) logits.
        ``valid_length`` (B,) marks right-padded rows: the positions past it
        change no real position's output. ``cache``: a cache view for
        incremental decoding, which then knows the valid lengths and gives
        each row's positions."""
        positions = None if cache is None \
            else cache.positions(self.max_length)
        x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x, valid_length=valid_length, cache=cache,
                      positions=positions)
        return self.head(self.norm_f(x))

    def generate(self, prompt, max_new_tokens=20):
        """Greedy decoding of one prompt through the cache views: the
        single-request case of ``serve.DecodeEngine``."""
        from ...serve.decode import cache

        return cache.generate(self, list(prompt), max_new_tokens,
                              lambda logits: int(logits.asnumpy().argmax()))


def axk1(config=None, experts_held=None, **overrides):
    """The net of ``config`` (default: the published A.X-K1 sizes) with
    ``overrides`` applied, e.g. ``num_hidden_layers=6, vocab_size=20480,
    dtype="bfloat16"`` with ``experts_held=(0, 12)`` for one chip's share."""
    cfg = dict(AX_K1 if config is None else config)
    dtype = overrides.pop("dtype", "float32")
    cfg.update(overrides)
    return AXK1Model(cfg, experts_held=experts_held, dtype=dtype)


def axk1_tiny(vocab_size=96, **overrides):
    """A few thousand parameters in the published proportions: a dense layer
    and two expert layers, 4 heads of 8 + 4 query/key sizes, 16 experts
    top-4, YaRN over an original context of 32."""
    cfg = dict(
        AX_K1, vocab_size=vocab_size, hidden_size=32, num_hidden_layers=3,
        intermediate_size=48, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, n_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=16, max_position_embeddings=1024,
        rope_scaling=dict(AX_K1["rope_scaling"], factor=8,
                          original_max_position_embeddings=32))
    return axk1(cfg, **overrides)
