"""GPT-style decoder-only causal language model (model-zoo LM family).

Reference scope: the transformer-LM example family the reference ships
(example/gluon/word_language_model + the transformer ops in
src/operator/contrib/transformer.cc) — rebuilt as a pre-LN causal decoder,
the architecture of GPT-2. TPU design notes:

- attention runs through the causal flash-attention path
  (ops/pallas_kernels.py) — O(T) memory, MXU-tiled; padded batches ride
  the same fused path via segment ids (``valid_length``);
- the whole forward is one jit under hybridize: static shapes, no
  KV-cache branching in the compiled graph;
- there is ONE forward pass. Incremental decoding hands it a cache view
  (``cache=``, see serve/decode/cache.py): the attention layer then calls
  ``cache.attend(layer, q, k, v)`` in place of the attention op, and how
  K/V is stored, written and attended over is the view's business. The
  fixed-shape programs the continuous-batching engine compiles ahead of
  time are this forward over three views;
- ``generate`` runs the engine's degenerate case by default (one request
  over a private page pool, O(T) per token); the fixed-width
  rolling-window re-forward (O(T²) work) is the ``use_cache=False``
  reference.
"""
from __future__ import annotations

import functools

import numpy as onp

from ... import initializer as init_mod
from ... import numpy_extension as npx
from ...base import MXNetError
from .. import nn
from ..block import HybridBlock

__all__ = ["GPTModel", "gpt2_small", "gpt2_medium", "gpt_tiny",
           "gpt_tp_rules"]


def _local_heads(num_heads):
    """Per-rank head count under an active tensor-parallel context (the
    identity without one — single-device graphs are untouched)."""
    from ...parallel import tp as _tp

    ctx = _tp.current()
    return ctx.local_heads(num_heads) if ctx is not None else num_heads


def gpt_tp_rules(mode="train", fsdp_axis="dp"):
    """Ordered partition rules declaring GPTModel's megatron layout.

    ``mode="train"``: column-parallel ``attn_qkv``/``ffn_1`` (weights AND
    biases; the fused QKV carries ``segments=3`` so each of Q/K/V splits
    per rank), ROW-parallel ``attn_proj``/``ffn_2`` weights, everything
    else dp-sharded (FSDP) via the catch-all.

    ``mode="serve"``: column-parallel only — merged activations are
    BITWISE the unsharded model's — with every other leaf replicated.
    """
    from jax.sharding import PartitionSpec as PS

    col = [
        (r"attn_qkv\.weight$", PS("tp", None), {"segments": 3}),
        (r"attn_qkv\.bias$", PS("tp"), {"segments": 3}),
        (r"ffn_1\.weight$", PS("tp", None)),
        (r"ffn_1\.bias$", PS("tp")),
    ]
    if mode == "serve":
        return tuple(col) + ((r".*", PS()),)
    row = [
        (r"attn_proj\.weight$", PS(None, "tp")),
        (r"ffn_2\.weight$", PS(None, "tp")),
    ]
    return tuple(col + row) + ((r".*", PS(fsdp_axis)),)


class DecoderLayer(HybridBlock):
    """Pre-LN causal transformer block (GPT-2 convention)."""

    def __init__(self, units=768, hidden_size=3072, num_heads=12,
                 dropout=0.1, layer_norm_eps=1e-5, dtype="float32",
                 **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError("units must be divisible by num_heads")
        self._num_heads = num_heads
        self._dropout = dropout
        self._layer = 0     # the layer a cache view stores this block under
        self.ln_1 = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.attn_qkv = nn.Dense(3 * units, flatten=False, dtype=dtype,
                                 weight_initializer=init_mod.Normal(0.02),
                                 in_units=units)
        self.attn_proj = nn.Dense(units, flatten=False, dtype=dtype,
                                  weight_initializer=init_mod.Normal(0.02),
                                  in_units=units)
        self.ln_2 = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.ffn_1 = nn.Dense(hidden_size, flatten=False, dtype=dtype,
                              weight_initializer=init_mod.Normal(0.02),
                              in_units=units)
        self.ffn_2 = nn.Dense(units, flatten=False, dtype=dtype,
                              weight_initializer=init_mod.Normal(0.02),
                              in_units=hidden_size)

    def _qkv(self, x):
        from ...parallel import tp as _tp

        h = self.ln_1(x)
        ctx = _tp.current()
        if ctx is not None and ctx.mode == "train":
            # megatron f at the attention region's entry: upstream (the
            # residual stream, norms, embeddings) receives the complete
            # tp-summed gradient
            h = _tp.tp_copy(h)
        qkv = self.attn_qkv(h)
        # under tp the local qkv is [Q_r | K_r | V_r] (segments=3 layout),
        # so thirds of the LOCAL width still split q/k/v correctly
        units = qkv.shape[-1] // 3
        q = npx.slice_axis(qkv, axis=-1, begin=0, end=units)
        k = npx.slice_axis(qkv, axis=-1, begin=units, end=2 * units)
        v = npx.slice_axis(qkv, axis=-1, begin=2 * units, end=3 * units)
        return q, k, v

    def _post_attention(self, x, attn):
        from ... import numpy as np
        from ...parallel import tp as _tp

        ctx = _tp.current()
        if ctx is None:
            attn = self.attn_proj(attn)
        elif ctx.mode == "train":
            # row-parallel attn_proj: the local W columns against the local
            # attn slice yield a partial sum; megatron g completes it. The
            # bias adds AFTER the psum so it counts once, not tp times
            attn = _tp.tp_sum(np.matmul(
                attn, self.attn_proj.weight.data().T)) \
                + self.attn_proj.bias.data()
        else:
            # serving: column-split heads merge by concatenation (bitwise
            # the unsharded activations), then the replicated projection
            attn = self.attn_proj(_tp.tp_gather(attn, dim=-1))
        if self._dropout:
            attn = npx.dropout(attn, p=self._dropout)
        x = x + attn
        h = self.ln_2(x)
        if ctx is not None and ctx.mode == "train":
            h = _tp.tp_copy(h)   # megatron f at the MLP region's entry
        up = npx.leaky_relu(self.ffn_1(h), act_type="gelu")
        if ctx is None:
            ffn = self.ffn_2(up)
        elif ctx.mode == "train":
            ffn = _tp.tp_sum(np.matmul(
                up, self.ffn_2.weight.data().T)) + self.ffn_2.bias.data()
        else:
            ffn = self.ffn_2(_tp.tp_gather(up, dim=-1))
        if self._dropout:
            ffn = npx.dropout(ffn, p=self._dropout)
        return x + ffn

    def forward(self, x, mask=None, cache=None):
        """``mask``: optional (B, 1, 1, T) key-padding mask (1 = attend).
        Combined with the causal mask on the fused flash path — without it
        pad keys are attended like real tokens. ``cache``: a cache view;
        it stores this layer's k/v and attends in the op's place (which
        keys a query sees is then the view's business, not ``mask``'s)."""
        q, k, v = self._qkv(x)
        if cache is None:
            attn = npx.multihead_attention(q, k, v, mask=mask,
                                           num_heads=_local_heads(
                                               self._num_heads),
                                           causal=True)
        else:
            attn = cache.attend(self._layer, q, k, v)
        return self._post_attention(x, attn)


class GPTModel(HybridBlock):
    """Token+position embeddings → N pre-LN causal blocks → tied LM head."""

    def __init__(self, vocab_size=50257, num_layers=12, units=768,
                 hidden_size=None, num_heads=12, max_length=1024,
                 dropout=0.1, tie_weights=True, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        hidden_size = hidden_size or 4 * units
        self.vocab_size = vocab_size
        self.max_length = max_length
        self._tie = tie_weights
        self._units = units
        self._num_heads = num_heads
        self._num_layers = num_layers
        self._dtype = dtype
        self.tok_embed = nn.Embedding(vocab_size, units, dtype=dtype)
        self.pos_embed = nn.Embedding(max_length, units, dtype=dtype)
        self.blocks = nn.HybridSequential()
        for i in range(num_layers):
            blk = DecoderLayer(units, hidden_size, num_heads, dropout,
                               dtype=dtype)
            blk._layer = i
            self.blocks.add(blk)
        self.ln_f = nn.LayerNorm(epsilon=1e-5, in_channels=units)
        self._dropout = dropout
        if not tie_weights:
            self.lm_head = nn.Dense(vocab_size, flatten=False,
                                    use_bias=False, dtype=dtype,
                                    in_units=units)

    # -- shared pieces ------------------------------------------------------
    def tp_partition_rules(self, mode="serve"):
        """The megatron layout of this architecture (see
        :func:`gpt_tp_rules`) — the hook ``serve.decode`` consults when a
        tensor-parallel engine is requested."""
        return gpt_tp_rules(mode)

    def _lm_logits(self, x):
        from ... import numpy as np

        if self._tie:
            # weight tying (Press & Wolf): logits = x · E^T
            return np.matmul(x, self.tok_embed.weight.data().T)
        return self.lm_head(x)

    def _pad_mask(self, valid_length, seq_len):
        """(B, 1, 1, T) key-padding mask for right-padded batches: True for
        positions < valid_length. Rides the fused flash path (segment ids)
        when combined with causal attention."""
        from ... import numpy as np

        ar = np.arange(seq_len, dtype="int32").reshape(1, seq_len)
        valid = valid_length.astype("int32").reshape(-1, 1)
        return (ar < valid).reshape(-1, 1, 1, seq_len)

    def _embed(self, tokens, pos):
        x = self.tok_embed(tokens) + self.pos_embed(pos)
        if self._dropout:
            x = npx.dropout(x, p=self._dropout)
        return x

    def cache_spec(self):
        """What a KV cache must hold for this model (on this rank, under
        tensor parallelism): the one thing serving asks besides
        ``forward``, ``max_length`` and ``tp_partition_rules``."""
        return {"layers": self._num_layers,
                "heads": _local_heads(self._num_heads),
                "head_dim": self._units // self._num_heads,
                "dtype": self._dtype}

    # -- the forward pass -----------------------------------------------------
    def forward(self, tokens, valid_length=None, cache=None):
        """Causal LM forward: logits (B, T, V). ``valid_length`` (B,)
        marks right-padded rows: pad keys (positions >= valid_length) are
        masked out of attention. Without it every position is treated as
        real — callers padding their batches must pass it or pad tokens
        leak into the context.

        ``cache``: a cache view (serve/decode/cache.py) for incremental
        decoding. It says where this call's tokens stand and attends in
        each layer's place, over what it holds; ``valid_length`` is then
        the view's to know."""
        from ... import numpy as np

        B, T = tokens.shape
        if cache is None:
            pos = np.arange(T, dtype="int32").reshape(1, T)
        else:
            pos = cache.positions(self.max_length)
        x = self._embed(tokens, pos)
        mask = None if valid_length is None \
            else self._pad_mask(valid_length, T)
        for blk in self.blocks:
            if cache is not None:
                x = blk(x, cache=cache)
            else:
                x = blk(x, mask) if mask is not None else blk(x)
        x = self.ln_f(x)
        return self._lm_logits(x)

    # -- generation ----------------------------------------------------------
    def _sample(self, logits, temperature):
        from ... import numpy as np
        from ... import random as rnd

        if temperature > 0:
            probs = npx.softmax(logits / temperature, axis=-1)
            return int(rnd.categorical(np.log(
                np.maximum(probs, 1e-20))).asnumpy())
        return int(logits.asnumpy().argmax())

    def generate(self, prompt, max_new_tokens=20, temperature=0.0,
                 window=None, use_cache=None):
        """Greedy / temperature sampling.

        ``use_cache=None`` (auto) decodes incrementally whenever the full
        sequence fits ``max_length`` — O(T) work per token, exact
        positions: the single-request case of the serve/decode engine
        (one prefill, then the one-token tick, eagerly over a private
        page pool). The fixed-width rolling-window loop
        (``use_cache=False``, or sequences past max_length) re-runs the
        whole window per token; its windows are right-padded and masked
        (``valid_length``), so pad tokens do not leak into attention. It
        is the reference the engine's tokens are compared with.
        """
        from ... import numpy as np

        if hasattr(prompt, "asnumpy"):
            prompt = prompt.asnumpy()
        toks = [int(t) for t in onp.asarray(prompt).ravel()]
        if max_new_tokens < 1:
            return toks
        total = len(toks) + max_new_tokens
        if use_cache is None:
            use_cache = total <= self.max_length
        if use_cache:
            if total > self.max_length:
                raise MXNetError(
                    f"use_cache generation needs prompt+new <= max_length="
                    f"{self.max_length}, got {total} — pass "
                    "use_cache=False for the rolling-window fallback")
            from ...serve.decode import cache

            return cache.generate(
                self, toks, max_new_tokens,
                functools.partial(self._sample, temperature=temperature))
        window = window or min(self.max_length, 64)
        for _ in range(max_new_tokens):
            ctx_toks = toks[-window:]
            L = len(ctx_toks)
            inp = onp.zeros((1, window), dtype="int32")
            inp[0, :L] = ctx_toks
            logits = self(np.array(inp),
                          np.array(onp.asarray([L], "int32")))[0, L - 1]
            toks.append(self._sample(logits, temperature))
        return toks


def gpt_tiny(vocab_size=1000, **kwargs):
    """Test/edge configuration."""
    kwargs.setdefault("num_layers", 2)
    kwargs.setdefault("units", 64)
    kwargs.setdefault("num_heads", 4)
    kwargs.setdefault("max_length", 128)
    return GPTModel(vocab_size=vocab_size, **kwargs)


def gpt2_small(vocab_size=50257, **kwargs):
    return GPTModel(vocab_size=vocab_size, num_layers=12, units=768,
                    num_heads=12, **kwargs)


def gpt2_medium(vocab_size=50257, **kwargs):
    return GPTModel(vocab_size=vocab_size, num_layers=24, units=1024,
                    num_heads=16, **kwargs)
