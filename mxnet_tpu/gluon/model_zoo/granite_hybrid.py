"""Granite 4.0-H: a decoder whose layers are Mamba-2 or attention by a list,
each followed by routed experts plus a shared MLP (model-zoo LM family).

Source: https://huggingface.co/ibm-granite/granite-4.0-h-small
(``model_type`` ``granitemoehybrid``). ``layer_types`` says which layers are
``mamba`` (a **Mamba-2** mixer: one input projection to ``[z | xBC | dt]``, a
causal depthwise conv with bias over ``xBC``, the state-space recurrence of
``ops/ssm.py`` with one group of ``B``/``C`` shared by all heads, a gated
RMSNorm over the whole inner width, the output projection) and which are
``attention`` (grouped-query softmax attention with NO position signal at
all: ``position_embedding_type`` ``nope``). Every layer's feed-forward part is
``parallel.moe.RoutedExperts`` (top-k over the router's full width, dropless,
told which experts it holds) plus a shared MLP that is ADDED, ungated. Plain
RMSNorm, tied head, and Granite's four multipliers::

    h = embedding_multiplier * E[tokens]
    h = h + residual_multiplier * mixer(norm_1(h))
    m = norm_2(h);  h = h + residual_multiplier * (moe(m) + shared(m))
    logits = norm_f(h) E^T / logits_scaling

with ``attention_multiplier`` the attention's scale (1/128 where
1/sqrt(128) would be usual).

There is ONE forward pass. Plain, it runs whole sequences from an empty
state. Handed a cache view (``cache=``, serve/decode/cache.py) the attention
layers call ``cache.attend`` and the Mamba-2 layers ``cache.recur``, which
hands them their rows' state (a conv tail and the SSM state) and keeps the new
one: the chunked scan in a prefill, the one-step update in a tick.
``cache_spec()`` states both kinds of state and two counters the routed
layers add to (token-expert pairs computed here; in ticks, held experts that
got a token).

The chip's share of an expert-parallel deployment is part of the model's
arguments, as in ``qwen3_next``: ``experts_held = (lo, hi)`` of the
``num_local_experts`` the router keeps, ``vocab_size`` the rows of the tied
embedding held, ``layer_types`` the layers held.

Departures from the published code, shared with the plain reference
(``benchmark/chip/chipbench/reference_granite_hybrid.py``): the experts' two
input matrices are one array (as published) and so are the shared MLP's; no
auxiliary loss; ``dt`` is not clamped (``time_step_limit`` (0, inf)).

The parts of a layer run under ``AttrScope(__scope__=...)`` names ``ssm``,
``attn``, ``router`` and ``experts``.
"""
from __future__ import annotations

import functools

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

__all__ = ["GraniteHybridModel", "granite_hybrid", "granite_hybrid_tiny",
           "GRANITE_4_0_H_SMALL"]

# the published config.json's keys that shape the model
GRANITE_4_0_H_SMALL = {
    "vocab_size": 100352, "hidden_size": 4096,
    "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    "num_attention_heads": 32, "num_key_value_heads": 8,
    "mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_chunk_size": 256,
    "num_local_experts": 72, "num_experts_per_tok": 10,
    "intermediate_size": 768, "shared_intermediate_size": 1536,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.0078125, "logits_scaling": 16,
    "rms_norm_eps": 1e-5, "max_position_embeddings": 131072,
}
GRANITE_4_0_H_SMALL["layer_types"] = GRANITE_4_0_H_SMALL["layer_types"] * 4


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw_normal(key, shape, dtype, sigma):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(sigma, dtype)


class _NormalAs(init_mod.Initializer):
    """``normal(0, sigma)`` drawn in the parameter's OWN dtype, in one
    program a shape: a bfloat16 model of billions of parameters is never
    held in float32, not even a leaf at a time. Fills whatever the
    parameter is called (the base class zeroes names ending in ``bias``)."""

    def __init__(self, sigma=0.02):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def init_array(self, name, arr):
        arr._set_data(_draw_normal(_random._next_key(), tuple(arr.shape),
                                   jnp.dtype(arr.dtype), self.sigma))


def _draw_dt_bias(shape, dt_min=1e-3, dt_max=1e-1, dt_floor=1e-4):
    """The inverse softplus of a step size drawn log-uniform in ``[dt_min,
    dt_max]``, as the Mamba-2 reference code draws it (state-spaces/mamba,
    ``mamba_ssm/modules/mamba2.py``): a head remembers tens to thousands of
    positions, where the transformers port's ``dt_bias = 1`` with ``A =
    -(1..H)`` forgets within two."""
    u = jax.random.uniform(_random._next_key(), shape, jnp.float32)
    dt = jnp.maximum(jnp.exp(u * (jnp.log(dt_max) - jnp.log(dt_min))
                             + jnp.log(dt_min)), dt_floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def _draw_conv(shape, d_conv):
    """Uniform in ``+-1/sqrt(d_conv)``: what that reference's depthwise
    ``nn.Conv1d`` draws for its weight and bias (fan-in ``d_conv``), so that
    ``B``, ``C`` and ``x`` are large enough for the state to count."""
    bound = d_conv ** -0.5
    return jax.random.uniform(_random._next_key(), shape, jnp.float32,
                              -bound, bound)


def _draw_A_log(shape, lo=1.0, hi=16.0):
    """``log A`` with ``A`` uniform in ``[1, 16]`` (the same reference's
    ``A_init_range``); the recurrence decays by ``exp(-dt A)``."""
    return jnp.log(jax.random.uniform(_random._next_key(), shape,
                                      jnp.float32, lo, hi))


class _Filled(init_mod.Initializer):
    """Fill with ``make(shape)``, whatever the parameter is called."""

    def __init__(self, make):
        super().__init__()
        self._make = make

    def init_array(self, name, arr):
        arr._set_data(self._make(arr.shape).astype(arr.dtype))


def _dense(units, in_units, dtype):
    return nn.Dense(units, use_bias=False, flatten=False, dtype=dtype,
                    weight_initializer=_NormalAs(0.02), in_units=in_units)


def _silu(x):
    return npx.activation(x, act_type="silu")


class RMSNorm(HybridBlock):
    """``x / sqrt(mean(x^2) + eps) * weight``, ``weight`` from 1; the mean
    in float32 whatever ``x`` is."""

    def __init__(self, units, eps=1e-5, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        self.weight = Parameter(shape=(units,), dtype=dtype, init="ones")

    def forward(self, x):
        return npx.rms_norm(x, self.weight.data(), eps=self._eps)


class Mamba2Mixer(HybridBlock):
    """The state-space mixer. ``state_index``: which of the model's
    recurrent layers this is (its state's place in a cache)."""

    def __init__(self, units, n_heads, d_head, d_state, d_conv=4, chunk=256,
                 eps=1e-5, state_index=0, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._h, self._p, self._n = n_heads, d_head, d_state
        self._inner = n_heads * d_head
        self._conv_width = self._inner + 2 * d_state     # one group
        self._chunk, self._eps = chunk, eps
        self._state_index = state_index
        self.in_proj = _dense(2 * self._inner + 2 * d_state + n_heads, units,
                              dtype)
        conv = _Filled(functools.partial(_draw_conv, d_conv=d_conv))
        self.conv_weight = Parameter(shape=(self._conv_width, d_conv),
                                     dtype=dtype, init=conv)
        self.conv_bias = Parameter(shape=(self._conv_width,), dtype=dtype,
                                   init=conv)
        self.dt_bias = Parameter(shape=(n_heads,), dtype="float32",
                                 init=_Filled(_draw_dt_bias))
        self.A_log = Parameter(shape=(n_heads,), dtype="float32",
                               init=_Filled(_draw_A_log))
        self.D = Parameter(shape=(n_heads,), dtype="float32", init="ones")
        self.norm_weight = Parameter(shape=(self._inner,), dtype=dtype,
                                     init="ones")
        self.out_proj = _dense(units, self._inner, dtype)

    def state_shapes(self, dtype):
        """What one sequence keeps between calls: the conv's last inputs
        and the SSM state (float32)."""
        d_conv = self.conv_weight.shape[1]
        return (((d_conv - 1, self._conv_width), dtype),
                ((self._h, self._p, self._n), "float32"))

    def forward(self, x, valid_length=None, cache=None):
        from ... import numpy as np

        # -1 for the batch: one traced graph serves every batch bucket
        T = x.shape[1]
        h, p, n, inner = self._h, self._p, self._n, self._inner
        zxbcdt = self.in_proj(x)
        z = npx.slice_axis(zxbcdt, axis=-1, begin=0, end=inner)
        xbc = npx.slice_axis(zxbcdt, axis=-1, begin=inner,
                             end=inner + self._conv_width)
        dt = npx.activation(
            npx.slice_axis(zxbcdt, axis=-1, begin=inner + self._conv_width,
                           end=None).astype("float32") + self.dt_bias.data(),
            act_type="softrelu")
        A = -np.exp(self.A_log.data())
        one_step = cache is not None and cache.decoding

        def step(tail, state, valid):
            conv, tail = npx.causal_conv1d_state(
                xbc, self.conv_weight.data(), self.conv_bias.data(), tail,
                valid, activation="silu")
            xs = np.reshape(npx.slice_axis(conv, axis=-1, begin=0, end=inner),
                            (-1, T, h, p))
            Bm = npx.slice_axis(conv, axis=-1, begin=inner, end=inner + n)
            Cm = npx.slice_axis(conv, axis=-1, begin=inner + n, end=None)
            if one_step:
                y, state = npx.ssd_step(
                    np.reshape(xs, (-1, h, p)), np.reshape(dt, (-1, h)), A,
                    np.reshape(Bm, (-1, n)), np.reshape(Cm, (-1, n)),
                    self.D.data(), state)
            else:
                y, state = npx.ssd_chunk_scan(
                    xs, dt, A, Bm, Cm, self.D.data(), state, valid,
                    chunk=self._chunk)
            return y, tail, state

        if cache is None:
            y = step(None, None, valid_length)[0]
        else:
            y = cache.recur(self._state_index, step)
        # the gate BEFORE the norm, whose mean runs over the whole width
        y = npx.rms_norm(np.reshape(y, (-1, T, inner)) * _silu(z),
                         self.norm_weight.data(), eps=self._eps)
        return self.out_proj(y)


class NoPEAttention(HybridBlock):
    """Causal grouped-query softmax attention with no position signal.
    ``kv_index``: which of the model's attention layers this is (its pages'
    place in a cache's pool)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim, scale,
                 kv_index=0, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._h, self._hkv, self._scale = num_heads, num_kv_heads, scale
        self._kv_index = kv_index
        self.q_proj = _dense(num_heads * head_dim, units, dtype)
        self.k_proj = _dense(num_kv_heads * head_dim, units, dtype)
        self.v_proj = _dense(num_kv_heads * head_dim, units, dtype)
        self.o_proj = _dense(units, num_heads * head_dim, dtype)

    def forward(self, x, valid_length=None, cache=None):
        from ... import numpy as np

        q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        if cache is not None:
            attn = cache.attend(self._kv_index, q, k, v, scale=self._scale)
        else:
            mask = None
            if valid_length is not None:
                T = x.shape[1]
                mask = (np.arange(T, dtype="int32").reshape(1, T)
                        < valid_length.astype("int32").reshape(-1, 1)) \
                    .reshape(-1, 1, 1, T)
            attn = npx.multihead_attention(
                q, k, v, mask=mask, num_heads=self._h,
                num_kv_heads=self._hkv, causal=True, scale=self._scale)
        return self.o_proj(attn)


class RoutedPlusShared(RoutedExperts):
    """``RoutedExperts`` (router, the experts held) plus the shared MLP,
    added ungated, on (B, T, units). Handed a cache view it adds to the
    model's counters: the token-expert pairs of real tokens computed here
    and, in a tick, the held experts that got one."""

    def __init__(self, units, expert_units, num_experts, top_k, shared_units,
                 experts_held=None, dtype="float32", norm_topk=True,
                 **kwargs):
        super().__init__(units, expert_units, num_experts, top_k,
                         experts_held=experts_held, norm_topk=norm_topk,
                         dtype=dtype, weight_initializer=_NormalAs(0.02),
                         **kwargs)
        self._shared_units = shared_units
        self.shared_in = _dense(2 * shared_units, units, dtype)
        self.shared_out = _dense(units, shared_units, dtype)

    def _count(self, cache, experts):
        from ... import numpy as np

        lo, hi = self.experts_held
        real = np.reshape(cache.token_mask(), (-1, 1))
        here = (experts >= lo) * (experts < hi) * real           # (N, k)
        cache.count("moe_pairs_here", np.sum(here.astype("int32")))
        if cache.decoding:
            held = np.arange(lo, hi, dtype="int32").reshape(1, 1, -1)
            got = np.max((np.expand_dims(experts, -1) == held)
                         * np.expand_dims(here, -1), axis=(0, 1))
            cache.count("moe_experts_touched", np.sum(got.astype("int32")))

    def forward(self, x, cache=None):
        from ... import numpy as np

        shape = x.shape
        xf = np.reshape(x, (-1, shape[-1]))
        with AttrScope(__scope__="router"):
            weights, experts = self.router(xf)
            if cache is not None:
                self._count(cache, experts)
        with AttrScope(__scope__="experts"):
            y = self.experts(xf, weights, experts)
            up = self.shared_in(xf)
            f = self._shared_units
            y = y + self.shared_out(
                _silu(npx.slice_axis(up, axis=-1, begin=0, end=f))
                * npx.slice_axis(up, axis=-1, begin=f, end=None))
        return np.reshape(y, (-1,) + tuple(shape[1:]))


class GraniteHybridLayer(HybridBlock):
    def __init__(self, cfg, kind, index, experts_held, dtype, **kwargs):
        super().__init__(**kwargs)
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        self.kind = kind
        self._residual = cfg["residual_multiplier"]
        self.norm_1 = RMSNorm(units, eps, dtype)
        if kind == "attention":
            heads = cfg["num_attention_heads"]
            self.mixer = NoPEAttention(
                units, heads, cfg["num_key_value_heads"], units // heads,
                cfg["attention_multiplier"], kv_index=index, dtype=dtype)
        else:
            self.mixer = Mamba2Mixer(
                units, cfg["mamba_n_heads"], cfg["mamba_d_head"],
                cfg["mamba_d_state"], cfg["mamba_d_conv"],
                cfg["mamba_chunk_size"], eps, state_index=index, dtype=dtype)
        self.norm_2 = RMSNorm(units, eps, dtype)
        self.moe = RoutedPlusShared(
            units, cfg["intermediate_size"], cfg["num_local_experts"],
            cfg["num_experts_per_tok"], cfg["shared_intermediate_size"],
            experts_held=experts_held, dtype=dtype)

    def forward(self, x, valid_length=None, cache=None):
        with AttrScope(__scope__="attn" if self.kind == "attention"
                       else "ssm"):
            x = x + self._residual * self.mixer(
                self.norm_1(x), valid_length=valid_length, cache=cache)
        return x + self._residual * self.moe(self.norm_2(x), cache=cache)


class GraniteHybridModel(HybridBlock):
    """Embedding (scaled) -> layers by ``layer_types`` -> RMSNorm -> tied
    head (scaled). ``config``: the published keys (``GRANITE_4_0_H_SMALL``)
    with ``layer_types`` and ``vocab_size`` as HELD here;
    ``num_local_experts`` stays the router's width and ``experts_held``
    (default: all) says which are held."""

    def __init__(self, config, experts_held=None, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        missing = sorted(set(GRANITE_4_0_H_SMALL) - set(config))
        if missing:
            raise MXNetError(f"GraniteHybridModel: the configuration lacks "
                             f"{missing}")
        self.config = {k: config[k] for k in GRANITE_4_0_H_SMALL}
        cfg = self.config
        kinds = list(cfg["layer_types"])
        if set(kinds) - {"mamba", "attention"}:
            raise MXNetError(f"layer_types {sorted(set(kinds))}: only "
                             "'mamba' and 'attention' are known")
        if cfg["mamba_n_groups"] != 1 or cfg["mamba_n_heads"] \
                * cfg["mamba_d_head"] != cfg["mamba_expand"] \
                * cfg["hidden_size"]:
            raise MXNetError(
                "GraniteHybridModel: one group of B/C and an inner width of "
                "mamba_n_heads x mamba_d_head = mamba_expand x hidden_size "
                "are what is built")
        self.experts_held = tuple(experts_held) if experts_held is not None \
            else (0, cfg["num_local_experts"])
        self.vocab_size = cfg["vocab_size"]
        self.max_length = cfg["max_position_embeddings"]   # no table: NoPE
        self._dtype = dtype
        self.embed = nn.Embedding(cfg["vocab_size"], cfg["hidden_size"],
                                  dtype=dtype,
                                  weight_initializer=_NormalAs(0.02))
        self.layers = nn.HybridSequential()
        seen = {"mamba": 0, "attention": 0}
        for kind in kinds:
            self.layers.add(GraniteHybridLayer(cfg, kind, seen[kind],
                                               self.experts_held, dtype))
            seen[kind] += 1
        self._n_kind = seen
        self.norm_f = RMSNorm(cfg["hidden_size"], cfg["rms_norm_eps"], dtype)

    def cache_spec(self):
        """What a cache must hold for this model: pages for the attention
        layers only (numbered among themselves), a conv tail and an SSM
        state a Mamba-2 layer and sequence, and the routed layers'
        counters."""
        cfg = self.config
        heads = cfg["num_attention_heads"]
        spec = {"layers": max(1, self._n_kind["attention"]),
                "heads": cfg["num_key_value_heads"],
                "head_dim": cfg["hidden_size"] // heads,
                "dtype": self._dtype,
                "counters": ("moe_pairs_here", "moe_experts_touched")}
        mamba = [l.mixer for l in self.layers if l.kind == "mamba"]
        if mamba:
            spec.update(state=mamba[0].state_shapes(self._dtype),
                        state_layers=len(mamba))
        return spec

    def forward(self, tokens, valid_length=None, cache=None):
        """(B, T) token ids of the vocabulary held -> (B, T, vocab) logits.
        ``valid_length`` (B,) marks right-padded rows: the positions past
        it change neither a real position's output nor the state.
        ``cache``: a cache view for incremental decoding, which then knows
        the valid lengths itself."""
        from ... import numpy as np

        cfg = self.config
        x = self.embed(tokens) * cfg["embedding_multiplier"]
        for layer in self.layers:
            x = layer(x, valid_length=valid_length, cache=cache)
        x = self.norm_f(x)
        return np.matmul(x, self.embed.weight.data().T) \
            / cfg["logits_scaling"]

    def generate(self, prompt, max_new_tokens=20):
        """Greedy decoding of one prompt through the cache views: the
        single-request case of ``serve.DecodeEngine``."""
        from ...serve.decode import cache

        return cache.generate(self, list(prompt), max_new_tokens,
                              lambda logits: int(logits.asnumpy().argmax()))


def granite_hybrid(config=None, experts_held=None, **overrides):
    """The net of ``config`` (default: the published Granite-4.0-H-Small
    sizes) with ``overrides`` applied, e.g. ``layer_types=[...10 layers],
    vocab_size=50176, dtype="bfloat16"`` with ``experts_held=(0, 36)`` for
    one chip's share."""
    cfg = dict(GRANITE_4_0_H_SMALL if config is None else config)
    dtype = overrides.pop("dtype", "float32")
    cfg.update(overrides)
    return GraniteHybridModel(cfg, experts_held=experts_held, dtype=dtype)


def granite_hybrid_tiny(vocab_size=96, **overrides):
    """A few thousand parameters in the published proportions: two Mamba-2
    layers, one attention layer, one more Mamba-2 layer; 12 experts top-4."""
    cfg = dict(
        GRANITE_4_0_H_SMALL, vocab_size=vocab_size, hidden_size=32,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
        mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=16,
        num_local_experts=12, num_experts_per_tok=4, intermediate_size=16,
        shared_intermediate_size=24, max_position_embeddings=256)
    return granite_hybrid(cfg, **overrides)
