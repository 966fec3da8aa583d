"""Qwen3-Next: a decoder whose layers are of two kinds by a pattern, each
followed by a mixture of experts (model-zoo LM family).

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct
(``model_type`` ``qwen3_next``). In periods of ``full_attention_interval``
layers, all but the last are **Gated DeltaNet** linear attention
(``npx.gated_delta_rule``: a recurrent d_k x d_v state a value head, computed
in chunks, fed by a short causal depthwise convolution) and the last is
**gated softmax attention** (grouped-query, QK-norm, RoPE on part of each
head, a sigmoid output gate). Every layer's feed-forward part is a **sparse
mixture of experts** (``parallel.moe.RoutedExperts``: top-k over the router's
full width, dropless, told which experts it holds) plus one shared expert
behind a sigmoid gate. RMSNorm is zero-centred (``1 + weight``); the head is
untied.

``x = x + mixer(norm_1(x)); x = x + moe(norm_2(x))``, then
``logits = norm_f(x) lm_head^T``.

The chip's share of an expert-parallel deployment is part of the model's
arguments, not a second code path: ``experts_held = (lo, hi)`` says which of
the ``num_experts`` this net holds in every layer (the router keeps its full
width; what the absent experts would add is left out), and ``vocab_size`` is
the rows of the embedding and the head that are held.

Departures from the published code, shared with the plain reference
(``benchmark/chip/chipbench/reference_qwen3_next.py``): ``in_proj_qkvz`` and
``in_proj_ba`` are split flat (``[q | k | v | z]``, ``[b | a]``) instead of
interleaved by key-head group; the routed experts' gate and up projections
are one array; no multi-token-prediction module; no auxiliary balancing
loss. Training only: there is no cached decode path (a cache manager that
holds recurrent state beside pages is ROADMAP work).

The four parts of a layer run under ``AttrScope(__scope__=...)`` names
``gdn``, ``attn``, ``router`` and ``experts``: in a compiled program they are
``jax.named_scope``s, so a device trace can be cut by them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import initializer as init_mod
from ... import numpy_extension as npx
from ... import random as _random
from ...attribute import AttrScope
from ...base import MXNetError
from ...parallel.moe import RoutedExperts
from .. import nn
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["Qwen3NextModel", "qwen3_next", "qwen3_next_tiny",
           "QWEN3_NEXT_80B_A3B"]

# the published config.json's keys that shape the model
QWEN3_NEXT_80B_A3B = {
    "vocab_size": 151936, "hidden_size": 2048, "num_hidden_layers": 48,
    "full_attention_interval": 4,
    "num_attention_heads": 16, "num_key_value_heads": 2, "head_dim": 256,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4,
    "num_experts": 512, "num_experts_per_tok": 10, "norm_topk_prob": True,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "rms_norm_eps": 1e-6,
}


def _dense(units, in_units, dtype):
    return nn.Dense(units, use_bias=False, flatten=False, dtype=dtype,
                    weight_initializer=init_mod.Normal(0.02),
                    in_units=in_units)


class _Exactly(init_mod.Initializer):
    """Fill with what ``make(key, shape)`` gives, whatever the parameter is
    called (the base class zeroes every name that ends in ``bias``)."""

    def __init__(self, make):
        super().__init__()
        self._make = make

    def init_array(self, name, arr):
        arr._set_data(self._make(_random._next_key(), arr.shape).astype(
            arr.dtype))


def _log_uniform(lo, hi):
    return _Exactly(lambda key, shape: jnp.log(
        jax.random.uniform(key, shape, minval=lo, maxval=hi)))


def _silu(x):
    return npx.activation(x, act_type="silu")


class ZeroCentredRMSNorm(HybridBlock):
    """``x / sqrt(mean(x^2) + eps) * (1 + weight)``, ``weight`` from 0."""

    def __init__(self, units, eps=1e-6, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        self.weight = Parameter(shape=(units,), dtype=dtype, init="zeros")

    def forward(self, x):
        return npx.rms_norm(x, self.weight.data() + 1.0, eps=self._eps)


class GatedDeltaNet(HybridBlock):
    """The linear-attention mixer: projections, causal conv and SiLU on
    q, k, v, the gated delta rule, a gated RMSNorm a head, the output
    projection."""

    def __init__(self, units, num_k_heads, num_v_heads, k_head_dim,
                 v_head_dim, conv_kernel=4, eps=1e-6, chunk=64,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if num_v_heads % num_k_heads:
            raise MXNetError(f"{num_v_heads} value heads are not a multiple "
                             f"of {num_k_heads} key heads")
        self._hk, self._hv = num_k_heads, num_v_heads
        self._dk, self._dv = k_head_dim, v_head_dim
        self._eps, self._chunk = eps, chunk
        kw, vw = num_k_heads * k_head_dim, num_v_heads * v_head_dim
        self.in_proj_qkvz = _dense(2 * kw + 2 * vw, units, dtype)
        self.in_proj_ba = _dense(2 * num_v_heads, units, dtype)
        self.conv_weight = Parameter(
            shape=(2 * kw + vw, conv_kernel), dtype=dtype,
            init=init_mod.Normal(0.02))
        self.A_log = Parameter(shape=(num_v_heads,), dtype="float32",
                               init=_log_uniform(1.0, 16.0))
        self.dt_bias = Parameter(
            shape=(num_v_heads,), dtype="float32",
            init=_Exactly(lambda key, shape: jnp.ones(shape)))
        self.norm_weight = Parameter(shape=(v_head_dim,), dtype=dtype,
                                     init="ones")
        self.out_proj = _dense(units, vw, dtype)

    def forward(self, x):
        from ... import numpy as np

        B, T = x.shape[0], x.shape[1]
        hk, hv, dk, dv = self._hk, self._hv, self._dk, self._dv
        kw, vw = hk * dk, hv * dv
        qkvz = self.in_proj_qkvz(x)
        z = npx.slice_axis(qkvz, axis=-1, begin=2 * kw + vw, end=None)
        qkv = npx.causal_conv1d(
            npx.slice_axis(qkvz, axis=-1, begin=0, end=2 * kw + vw),
            self.conv_weight.data(), activation="silu")
        q = npx.slice_axis(qkv, axis=-1, begin=0, end=kw)
        k = npx.slice_axis(qkv, axis=-1, begin=kw, end=2 * kw)
        v = npx.slice_axis(qkv, axis=-1, begin=2 * kw, end=None)
        ba = self.in_proj_ba(x)
        beta = npx.sigmoid(npx.slice_axis(ba, axis=-1, begin=0, end=hv))
        a = npx.slice_axis(ba, axis=-1, begin=hv, end=None)
        g = -np.exp(self.A_log.data()) * npx.activation(
            a + self.dt_bias.data(), act_type="softrelu")
        o = npx.gated_delta_rule(
            np.reshape(q, (B, T, hk, dk)), np.reshape(k, (B, T, hk, dk)),
            np.reshape(v, (B, T, hv, dv)), g, beta, chunk=self._chunk)
        y = npx.rms_norm(o, self.norm_weight.data(), eps=self._eps) \
            * _silu(np.reshape(z, (B, T, hv, dv)))
        return self.out_proj(np.reshape(y, (B, T, vw)))


class GatedAttention(HybridBlock):
    """Causal grouped-query softmax attention with QK-norm, RoPE on the
    first ``rotary_dim`` of each head and a sigmoid gate on the output."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, rotary_dim,
                 rope_theta, eps=1e-6, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._h, self._hkv, self._d = num_heads, num_kv_heads, head_dim
        self._rot, self._theta = rotary_dim, float(rope_theta)
        self.q_proj = _dense(2 * num_heads * head_dim, units, dtype)
        self.k_proj = _dense(num_kv_heads * head_dim, units, dtype)
        self.v_proj = _dense(num_kv_heads * head_dim, units, dtype)
        self.q_norm = ZeroCentredRMSNorm(head_dim, eps, dtype)
        self.k_norm = ZeroCentredRMSNorm(head_dim, eps, dtype)
        self.o_proj = _dense(units, num_heads * head_dim, dtype)

    def forward(self, x):
        from ... import numpy as np

        B, T = x.shape[0], x.shape[1]
        h, hkv, d = self._h, self._hkv, self._d
        qg = np.reshape(self.q_proj(x), (B, T, h, 2 * d))
        q = npx.slice_axis(qg, axis=-1, begin=0, end=d)
        gate = npx.slice_axis(qg, axis=-1, begin=d, end=None)
        k = np.reshape(self.k_proj(x), (B, T, hkv, d))
        q = npx.rope(self.q_norm(q), rotary_dim=self._rot, theta=self._theta)
        k = npx.rope(self.k_norm(k), rotary_dim=self._rot, theta=self._theta)
        attn = npx.multihead_attention(
            np.reshape(q, (B, T, h * d)), np.reshape(k, (B, T, hkv * d)),
            self.v_proj(x), num_heads=h, num_kv_heads=hkv, causal=True,
            scale=d ** -0.5)
        return self.o_proj(attn * npx.sigmoid(np.reshape(gate,
                                                         (B, T, h * d))))


class SparseMoE(RoutedExperts):
    """``RoutedExperts`` (router, the experts held, the token counts) plus
    the shared expert behind its sigmoid gate, on (B, T, units)."""

    def __init__(self, units, expert_units, num_experts, top_k,
                 shared_units, experts_held=None, norm_topk=True,
                 dtype="float32", **kwargs):
        super().__init__(units, expert_units, num_experts, top_k,
                         experts_held=experts_held, norm_topk=norm_topk,
                         dtype=dtype, **kwargs)
        self.shared_gate_proj = _dense(shared_units, units, dtype)
        self.shared_up_proj = _dense(shared_units, units, dtype)
        self.shared_down_proj = _dense(units, shared_units, dtype)
        self.shared_gate = _dense(1, units, dtype)

    def forward(self, x):
        from ... import numpy as np

        shape = x.shape
        xf = np.reshape(x, (-1, shape[-1]))
        with AttrScope(__scope__="router"):
            weights, experts = self.router(xf)
        with AttrScope(__scope__="experts"):
            y = self.experts(xf, weights, experts)
            shared = self.shared_down_proj(
                _silu(self.shared_gate_proj(xf)) * self.shared_up_proj(xf))
            y = y + npx.sigmoid(self.shared_gate(xf)) * shared
        return np.reshape(y, shape)


class Qwen3NextLayer(HybridBlock):
    def __init__(self, cfg, full_attention, experts_held, dtype, **kwargs):
        super().__init__(**kwargs)
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.full_attention = full_attention
        self.norm_1 = ZeroCentredRMSNorm(units, eps, dtype)
        if full_attention:
            self.mixer = GatedAttention(
                units, cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"],
                int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
                cfg["rope_theta"], eps, dtype)
        else:
            self.mixer = GatedDeltaNet(
                units, cfg["linear_num_key_heads"],
                cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
                eps, dtype=dtype)
        self.norm_2 = ZeroCentredRMSNorm(units, eps, dtype)
        self.moe = SparseMoE(
            units, cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"],
            cfg["shared_expert_intermediate_size"],
            experts_held=experts_held, norm_topk=cfg["norm_topk_prob"],
            dtype=dtype)

    def forward(self, x):
        with AttrScope(__scope__="attn" if self.full_attention else "gdn"):
            x = x + self.mixer(self.norm_1(x))
        return x + self.moe(self.norm_2(x))


class Qwen3NextModel(HybridBlock):
    """Embedding -> layers by the pattern -> zero-centred RMSNorm -> untied
    head. ``config``: the published keys (``QWEN3_NEXT_80B_A3B``), with
    ``num_hidden_layers`` and ``vocab_size`` as HELD here; ``num_experts``
    stays the router's width and ``experts_held`` (default: all) says which
    are held."""

    def __init__(self, config, experts_held=None, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        missing = sorted(set(QWEN3_NEXT_80B_A3B) - set(config))
        if missing:
            raise MXNetError(f"Qwen3NextModel: the configuration lacks "
                             f"{missing}")
        self.config = {k: config[k] for k in QWEN3_NEXT_80B_A3B}
        cfg = self.config
        self.experts_held = tuple(experts_held) if experts_held is not None \
            else (0, cfg["num_experts"])
        self.vocab_size = cfg["vocab_size"]
        units = cfg["hidden_size"]
        self.embed = nn.Embedding(cfg["vocab_size"], units, dtype=dtype,
                                  weight_initializer=init_mod.Normal(0.02))
        self.layers = nn.HybridSequential()
        for i in range(cfg["num_hidden_layers"]):
            self.layers.add(Qwen3NextLayer(
                cfg, (i + 1) % cfg["full_attention_interval"] == 0,
                self.experts_held, dtype))
        self.norm_f = ZeroCentredRMSNorm(units, cfg["rms_norm_eps"], dtype)
        self.lm_head = _dense(cfg["vocab_size"], units, dtype)

    def forward(self, tokens):
        """(B, T) token ids of the vocabulary held -> (B, T, vocab) logits."""
        x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x)
        return self.lm_head(self.norm_f(x))


def qwen3_next(config=None, experts_held=None, **overrides):
    """The net of ``config`` (default: the published 80B-A3B sizes) with
    ``overrides`` applied, e.g. ``num_hidden_layers=4, vocab_size=18992``
    with ``experts_held=(0, 32)`` for one chip's share."""
    cfg = dict(QWEN3_NEXT_80B_A3B if config is None else config)
    dtype = overrides.pop("dtype", "float32")
    cfg.update(overrides)
    return Qwen3NextModel(cfg, experts_held=experts_held, dtype=dtype)


def qwen3_next_tiny(vocab_size=96, **overrides):
    """A few thousand parameters in the published proportions: one period of
    three Gated DeltaNet layers and one attention layer, 16 experts top-4."""
    cfg = dict(
        QWEN3_NEXT_80B_A3B, vocab_size=vocab_size, hidden_size=32,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=16,
        shared_expert_intermediate_size=16)
    return qwen3_next(cfg, **overrides)
