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
- incremental decode is a SEPARATE pair of fixed-shape paths
  (``forward_prefill`` / ``forward_decode``) over a preallocated
  ``[slots, layers, heads, max_len, head_dim]`` KV cache — the graphs the
  continuous-batching engine (serve/decode) compiles ahead of time;
- ``generate`` routes through the cached incremental path by default
  (O(T) per token); the legacy fixed-width rolling-window re-forward
  (O(T²) work) survives as the ``use_cache=False`` fallback.
"""
from __future__ import annotations

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

    def forward(self, x, mask=None):
        """``mask``: optional (B, 1, 1, T) key-padding mask (1 = attend).
        Combined with the causal mask on the fused flash path — without it
        pad keys are attended like real tokens."""
        out, _, _ = self.forward_prefill(x, mask)
        return out

    def forward_prefill(self, x, mask=None):
        """Full-sequence forward that also returns this layer's k/v
        (B, T, units) for KV-cache seeding. Runs the exact compute of
        ``forward`` — prefill and the plain forward cannot drift."""
        q, k, v = self._qkv(x)
        attn = npx.multihead_attention(q, k, v, mask=mask,
                                       num_heads=_local_heads(
                                           self._num_heads),
                                       causal=True)
        return self._post_attention(x, attn), k, v

    def forward_decode(self, x, k_cache, v_cache, write_mask, kv_mask):
        """One-token incremental step against this layer's cache.

        x : (B, 1, units) current-token hidden state.
        k_cache / v_cache : (B, max_len, units) — the slot cache in
            flat (pre-head-split) layout.
        write_mask : (B, max_len, 1) bool, True exactly at each row's
            write position — the new k/v lands there.
        kv_mask : (B, 1, 1, max_len) bool marking readable cache entries
            (positions <= the write position), so stale/unwritten tail
            entries never leak into attention.
        Returns (out, k_cache', v_cache').
        """
        from ... import numpy as np

        q, k, v = self._qkv(x)
        k_cache = np.where(write_mask, k, k_cache)
        v_cache = np.where(write_mask, v, v_cache)
        attn = npx.multihead_attention(q, k_cache, v_cache, mask=kv_mask,
                                       num_heads=_local_heads(
                                           self._num_heads),
                                       causal=False)
        return self._post_attention(x, attn), k_cache, v_cache


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
        for _ in range(num_layers):
            self.blocks.add(DecoderLayer(units, hidden_size, num_heads,
                                         dropout, dtype=dtype))
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

    def _split_heads(self, x):
        """(B, T, units) -> (B, heads, T, head_dim) — the KV-cache layout.
        Head count derives from the ACTUAL width so tensor-parallel local
        slices (units/tp, heads/tp, same head_dim) split correctly."""
        from ... import numpy as np

        T = x.shape[1]
        d = self._units // self._num_heads
        return np.transpose(
            np.reshape(x, (-1, T, x.shape[-1] // d, d)), (0, 2, 1, 3))

    def _merge_heads(self, x):
        """(B, heads, T, head_dim) -> (B, T, units) — shape-derived, so a
        tensor-parallel local (heads/tp) stack merges to units/tp."""
        from ... import numpy as np

        T = x.shape[2]
        return np.reshape(np.transpose(x, (0, 2, 1, 3)),
                          (-1, T, x.shape[1] * x.shape[3]))

    def _embed(self, tokens, pos):
        x = self.tok_embed(tokens) + self.pos_embed(pos)
        if self._dropout:
            x = npx.dropout(x, p=self._dropout)
        return x

    # -- full-sequence forward ----------------------------------------------
    def forward(self, tokens, valid_length=None):
        """Causal LM forward. ``valid_length`` (B,) marks right-padded rows:
        pad keys (positions >= valid_length) are masked out of attention.
        Without it every position is treated as real — callers padding
        their batches must pass it or pad tokens leak into the context."""
        from ... import numpy as np

        B, T = tokens.shape
        pos = np.arange(T, dtype="int32").reshape(1, T)
        x = self._embed(tokens, pos)
        mask = None if valid_length is None \
            else self._pad_mask(valid_length, T)
        for blk in self.blocks:
            x = blk(x, mask) if mask is not None else blk(x)
        x = self.ln_f(x)
        return self._lm_logits(x)

    # -- incremental decode (KV cache) --------------------------------------
    def init_cache(self, batch, max_len):
        """Preallocated KV cache pair, each
        [batch(slots), layers, heads, max_len, head_dim]."""
        from ... import numpy as np

        if max_len > self.max_length:
            raise MXNetError(
                f"cache max_len {max_len} exceeds the position table "
                f"max_length={self.max_length}")
        d = self._units // self._num_heads
        shape = (batch, self._num_layers, _local_heads(self._num_heads),
                 max_len, d)
        return (np.zeros(shape, dtype=self._dtype),
                np.zeros(shape, dtype=self._dtype))

    def forward_prefill(self, tokens, valid_length):
        """Process whole (right-padded) prompts once and seed a KV cache.

        tokens : (B, T) int32, right-padded; valid_length : (B,) int32.
        Returns (last_logits (B, V) — logits at each row's final valid
        position, k (B, layers, heads, T, head_dim), v (same)). K/V rows
        past valid_length hold garbage the decode masks never read.
        """
        from ... import numpy as np

        B, T = tokens.shape
        pos = np.arange(T, dtype="int32").reshape(1, T)
        x = self._embed(tokens, pos)
        mask = self._pad_mask(valid_length, T)
        ks, vs = [], []
        for blk in self.blocks:
            x, k, v = blk.forward_prefill(x, mask)
            ks.append(self._split_heads(k))
            vs.append(self._split_heads(v))
        x = self.ln_f(x)
        logits = self._lm_logits(x)                       # (B, T, V)
        onehot = np.one_hot(valid_length.astype("int32") - 1, T,
                            dtype=str(logits.dtype))      # (B, T)
        last = np.einsum("btv,bt->bv", logits, onehot)
        return last, np.stack(ks, axis=1), np.stack(vs, axis=1)

    def forward_decode(self, tokens, positions, k_cache, v_cache):
        """One decode tick: one new token per cache row.

        tokens : (S,) int32 — each row's previous token.
        positions : (S,) int32 — each row's write position (= current
            length); the new k/v lands there and attention reads
            positions <= it.
        k_cache / v_cache : [S, layers, heads, max_len, head_dim].
        Returns (logits (S, V), k_cache', v_cache'). Fixed shapes — the
        decode engine compiles this ONCE and replays it every tick.
        """
        from ... import numpy as np

        L = k_cache.shape[3]
        pos2 = positions.astype("int32").reshape(-1, 1)
        x = self._embed(tokens.reshape(-1, 1),
                        np.minimum(pos2, self.max_length - 1))
        ar = np.arange(L, dtype="int32").reshape(1, L)
        write_mask = (ar == pos2).reshape(-1, L, 1)
        kv_mask = (ar <= pos2).reshape(-1, 1, 1, L)
        nk, nv = [], []
        for i, blk in enumerate(self.blocks):
            kc = self._merge_heads(np.squeeze(
                npx.slice_axis(k_cache, axis=1, begin=i, end=i + 1), axis=1))
            vc = self._merge_heads(np.squeeze(
                npx.slice_axis(v_cache, axis=1, begin=i, end=i + 1), axis=1))
            x, kc, vc = blk.forward_decode(x, kc, vc, write_mask, kv_mask)
            nk.append(self._split_heads(kc))
            nv.append(self._split_heads(vc))
        x = self.ln_f(x)
        logits = self._lm_logits(x)                       # (S, 1, V)
        return (np.squeeze(logits, axis=1),
                np.stack(nk, axis=1), np.stack(nv, axis=1))

    # -- paged incremental decode (vLLM-style page pool) ---------------------
    #
    # The paged variants replace the per-slot [max_len] reservation with a
    # shared pool of fixed-size pages, each [page_tokens] positions of one
    # layer-stack:  pool shape [num_pages, layers, heads, head_dim,
    # page_tokens].  A page of one layer keeps its positions along the
    # LAST axis: that is the layout the chip gives the pool anyway
    # (head_dim 64 is half a vector register's lanes, so it made
    # ``page_tokens`` the fastest axis of the older [.., page_tokens,
    # head_dim] shape), and declared so, the decode kernel takes the pool
    # as it stands, a page of a layer being one contiguous block in which
    # ``q . K`` leaves the positions along the lanes.  A slot's cache is
    # an int32 page-table ROW of width W+1 = ceil(max_len/page_tokens)+1
    # mapping logical page index -> pool page id; the sentinel id
    # ``num_pages`` (one past the pool) marks unmapped columns.
    #
    # Reads.  The tick never gathers: ``npx.paged_decode_attention`` walks
    # the pages a slot's row maps, up to the slot's length, where they lie
    # in the pool (Pallas kernel ``mxtpu_paged_decode`` on the chip, a
    # gather + mask + softmax of the same numbers elsewhere).  Only the
    # prefix join, with up to a bucket of queries a row, still gathers the
    # row's first W columns into a contiguous [W*P] view for the dense
    # masked attention (``_gather_page_view``; the sentinel clips to a
    # real page whose positions the mask always excludes).
    #
    # Writes are indexed updates of the (donated) pool, in place and a
    # WHOLE PAGE of one or all layers at a time: ``np.index_update`` at
    # the page ids the table maps.  (An update of a single position makes
    # XLA lay the whole pool out anew and back, an update of whole pages
    # does not.)  The tick therefore reads the pages its rows land in,
    # puts the rows in and writes the pages back.  A write routed at the
    # sentinel id (an unmapped column, an inactive slot, a chunk past
    # ``valid_length``) is out of range — one past the end, never negative
    # — and jax's ``.at[].set`` drops out-of-range updates, so it vanishes
    # exactly instead of corrupting a live page.  The tick and the prefix
    # join write each layer's k/v BEFORE that layer's attention, so the
    # pool already holds the new positions.  Nothing but the updates has
    # the pool's shape, and all three programs keep fully static shapes,
    # preserving the zero-recompile serving contract.

    def init_paged_cache(self, num_pages, page_tokens):
        """Preallocated paged KV pool pair, each
        [num_pages, layers, heads, head_dim, page_tokens]."""
        from ... import numpy as np

        d = self._units // self._num_heads
        shape = (int(num_pages), self._num_layers,
                 _local_heads(self._num_heads), d, int(page_tokens))
        return (np.zeros(shape, dtype=self._dtype),
                np.zeros(shape, dtype=self._dtype))

    @staticmethod
    def _layer_id(i):
        """Layer ``i`` as an int32 scalar ARRAY: an index of the pool that
        is an operand, so that one eager program serves every layer when
        these bodies are traced (an int in the key is a program a
        layer). In the compiled graph it is a constant all the same."""
        from ... import numpy as np

        return np.array(i, dtype="int32")

    def _gather_page_view(self, pool, layer, flat_ids, W):
        """Gather page-table rows (W columns each, flattened into
        ``flat_ids``) of layer ``layer`` (a ``_layer_id``) straight from
        the pool (no slice of the layer is made first) into a contiguous
        (rows, W*P, units) kv view; the sentinel clamps to the last page.
        Batch-polymorphic: one traced graph serves every batch bucket, so
        no reshape may bake the row count."""
        from ... import numpy as np

        H, D, P = pool.shape[2:]
        view = pool[flat_ids, layer]                     # (rows*W, H, D, P)
        view = np.transpose(np.reshape(view, (-1, W, H, D, P)),
                            (0, 1, 4, 2, 3))
        return np.reshape(view, (-1, W * P, H * D))

    def _scatter_pages(self, k, v, valid_length, start, page_table,
                       k_pool, v_pool, layer=None):
        """Write prompt k/v into the pool, whole pages at a time, with one
        indexed update per pool: (B, layers, heads, T, head_dim) of every
        layer, or (B, heads, T, head_dim) of layer ``layer`` (a
        ``_layer_id``).

        Chunk j of a row lands in the page its ``page_table`` row maps
        for logical page ``start//P + j``. A chunk past ``valid_length``
        is routed at the sentinel id, like one whose table column holds
        it, and the update drops both. The engine never maps one page to
        two rows of a batch, so no two chunks share a page."""
        from ... import numpy as np

        NP_, P = k_pool.shape[0], k_pool.shape[4]
        T = k.shape[-2]
        W = page_table.shape[1] - 1
        J = -(-T // P)
        j_idx = np.arange(J, dtype="int32").reshape(1, J)
        valid = valid_length.astype("int32").reshape(-1, 1)
        # (valid * 0, not zeros: stays an op ON the input, so the traced
        # graph keeps the batch dim symbolic across buckets)
        base = (start.astype("int32") // P).reshape(-1, 1) \
            if start is not None else valid * 0
        page_id = np.take_along_axis(
            page_table, np.minimum(base + j_idx, W), axis=1)     # (B, J)
        page_id = np.reshape(np.where(j_idx * P < valid, page_id, NP_), (-1,))
        key = (page_id,) if layer is None else (page_id, layer)
        return (self._update_pool(k_pool, key, self._page_chunks(k, J, P)),
                self._update_pool(v_pool, key, self._page_chunks(v, J, P)))

    @staticmethod
    def _update_pool(pool, key, value):
        """``pool.at[key].set(value)`` of whole pages, waited for. In a
        compiled program the update is in place. These bodies also run
        EAGERLY, once, when a program is traced, and there every update
        is a copy of the pool: without the wait the host runs layers
        ahead of the device with a pool-sized buffer in flight for each
        (the trace of a 3 GiB pool pair peaked at 15.1 of a v5e's 15.75
        GiB)."""
        from ... import numpy as np

        return np.index_update(pool, key, value).wait_to_read()

    @staticmethod
    def _page_chunks(x, J, P):
        """(B, ..., T, D) -> (B*J, ..., D, P): T zero-padded to J pages,
        the page axis moved forward and each page's positions last, as
        the pool keeps them. -1 keeps the graph batch-polymorphic across
        compile-time batch buckets."""
        from ... import numpy as np

        inner, (T, D) = tuple(x.shape[1:-2]), x.shape[-2:]
        if J * P != T:
            x = np.pad(x, ((0, 0),) * (x.ndim - 2)
                       + ((0, J * P - T), (0, 0)))
        x = np.moveaxis(np.reshape(x, (-1,) + inner + (J, P, D)), -3, 1)
        return np.reshape(np.swapaxes(x, -1, -2), (-1,) + inner + (D, P))

    def _write_rows(self, pool, layer, page_id, hits, rows):
        """Put ``rows`` (S, K, heads, head_dim) into layer ``layer`` (a
        ``_layer_id``) of the pages ``page_id`` (S*J,): read the pages,
        set row k wherever ``hits[k]`` (S, J, 1, 1, P) says, write them
        back. A page that no row hits goes back as it came."""
        from ... import numpy as np

        S, K, H, D = rows.shape
        old = pool[page_id, layer]              # (S*J, H, D, P); clamps
        new = np.reshape(old, (S, -1) + tuple(old.shape[1:]))
        for k in range(K):
            new = np.where(hits[k], rows[:, k].reshape(S, 1, H, D, 1), new)
        return self._update_pool(pool, (page_id, layer),
                                 np.reshape(new, old.shape))

    def forward_prefill_paged(self, tokens, valid_length, page_table,
                              k_pool, v_pool):
        """Whole-prompt prefill into a paged pool (prompts starting at
        position 0 — the no-shared-prefix case).

        Runs the EXACT flash-path compute of ``forward_prefill`` (the
        last-valid logits are bitwise those of the slot-cache engine);
        only the cache write changes: the k/v of all layers, cut into
        whole pages, lands in the pages ``page_table`` (B, W+1) maps.
        Returns (last_logits (B, V), k_pool', v_pool').
        """
        last, k, v = self.forward_prefill(tokens, valid_length)
        k_pool, v_pool = self._scatter_pages(
            k, v, valid_length, None, page_table, k_pool, v_pool)
        return last, k_pool, v_pool

    def forward_prefill_join(self, tokens, valid_length, start, page_table,
                             k_pool, v_pool):
        """Suffix prefill joining a cached prefix at page-aligned offset
        ``start`` (B,): the radix prefix-cache hit path.

        ``tokens`` (B, T) holds only the prompt SUFFIX (right-padded,
        ``valid_length`` real tokens); positions start..start+T-1. Each
        layer first writes the suffix's k/v into pages start//P + j of
        the pool, then each query attends the gathered page view — the
        prefix already in the pool plus the suffix just written — masked
        to absolute positions <= its own. (Up to a bucket of queries a
        row is a matrix-unit problem: the dense masked attention over the
        view stays, where the tick's one to K queries a slot read the
        pages in place.)
        Returns (last_logits (B, V), k_pool', v_pool').
        """
        from ... import numpy as np

        P = k_pool.shape[4]
        B, T = tokens.shape
        W = page_table.shape[1] - 1
        WP = W * P
        start = start.astype("int32")
        pos = start.reshape(-1, 1) + np.arange(T, dtype="int32").reshape(1, T)
        x = self._embed(tokens, np.minimum(pos, self.max_length - 1))
        ar = np.arange(WP, dtype="int32").reshape(1, 1, WP)
        mask = (ar <= pos.reshape(-1, T, 1)).reshape(-1, 1, T, WP)
        flat_ids = np.reshape(
            npx.slice_axis(page_table, axis=1, begin=0, end=W), (-1,))
        for i, blk in enumerate(self.blocks):
            lay = self._layer_id(i)
            q, k, v = blk._qkv(x)
            k_pool, v_pool = self._scatter_pages(
                self._split_heads(k), self._split_heads(v), valid_length,
                start, page_table, k_pool, v_pool, layer=lay)
            viewk = self._gather_page_view(k_pool, lay, flat_ids, W)
            viewv = self._gather_page_view(v_pool, lay, flat_ids, W)
            attn = npx.multihead_attention(q, viewk, viewv, mask=mask,
                                           num_heads=_local_heads(
                                               self._num_heads),
                                           causal=False)
            x = blk._post_attention(x, attn)
        x = self.ln_f(x)
        logits = self._lm_logits(x)                              # (B, T, V)
        onehot = np.one_hot(valid_length.astype("int32") - 1, T,
                            dtype=str(logits.dtype))
        last = np.einsum("btv,bt->bv", logits, onehot)
        return last, k_pool, v_pool

    def forward_decode_paged(self, tokens, positions, page_table,
                             k_pool, v_pool):
        """One multi-token decode tick against the paged pool.

        tokens : (S, K) int32 — column 0 is each row's last committed
            token, columns 1..K-1 a draft continuation (K=1: the plain
            single-token tick).
        positions : (S,) int32 — column 0's write position (= current
            length); column i lands at positions + i.
        page_table : (S, W+1) int32 row per slot (sentinel = num_pages).

        Each layer writes its S*K new k/v rows into the pool first (a
        read-modify-write of the pages they land in, ``_write_rows``)
        and then attends the pool itself, which already holds them:
        ``npx.paged_decode_attention`` walks the pages each slot's row
        maps, up to its length, and query i reads positions <=
        positions + i. A row whose page id is the sentinel (an inactive
        slot, a position past the table) writes nothing, and a slot with
        no mapped page attends nothing (its logits are those of a zero
        attention output; the engine never reads them).
        Returns (logits (S, K, V), k_pool', v_pool') where logits[:, i]
        scores the token AFTER tokens[:, i] — greedy verification accepts
        the longest draft prefix that matches argmax(logits).
        """
        from ... import numpy as np

        H, D, P = k_pool.shape[2:]
        S, K = tokens.shape
        W = page_table.shape[1] - 1
        positions = positions.astype("int32")
        pos2 = positions.reshape(-1, 1)
        q_pos = pos2 + np.arange(K, dtype="int32").reshape(1, K)  # (S, K)
        x = self._embed(tokens, np.minimum(q_pos, self.max_length - 1))
        # pool write routing (shared by every layer): the J pages a
        # slot's K rows can land in, and for each row the cell it takes
        # (the cell whose position is k past the slot's)
        J = 1 + -(-(K - 1) // P)
        col = pos2 // P + np.arange(J, dtype="int32").reshape(1, J)  # (S, J)
        page_id = np.reshape(np.take_along_axis(
            page_table, np.minimum(col, W), axis=1), (-1,))
        past = (col * P - pos2).reshape(S, J, 1, 1, 1) \
            + np.arange(P, dtype="int32").reshape(1, 1, 1, 1, P)
        hits = [past == k for k in range(K)]
        for i, blk in enumerate(self.blocks):
            lay = self._layer_id(i)
            q, k, v = blk._qkv(x)
            k_pool = self._write_rows(k_pool, lay, page_id, hits,
                                      np.reshape(k, (S, K, H, D)))
            v_pool = self._write_rows(v_pool, lay, page_id, hits,
                                      np.reshape(v, (S, K, H, D)))
            attn = npx.paged_decode_attention(
                np.reshape(q, (S, K, H, D)), k_pool, v_pool, lay,
                page_table, positions)
            x = blk._post_attention(x, attn)
        x = self.ln_f(x)
        return self._lm_logits(x), k_pool, v_pool                 # (S, K, V)

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

        ``use_cache=None`` (auto) routes through the incremental KV-cache
        path whenever the full sequence fits ``max_length`` — O(T) work
        per token, exact positions, one fixed-shape step program. The
        legacy fixed-width rolling-window loop (``use_cache=False``, or
        sequences past max_length) re-runs the whole window per token;
        its windows are right-padded and masked (``valid_length``), so
        pad tokens no longer leak into attention.
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
            return self._generate_cached(toks, max_new_tokens, temperature)
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

    def _generate_cached(self, toks, max_new_tokens, temperature):
        """Single-request degenerate case of the serve/decode engine:
        prefill once, then replay the fixed-shape decode step."""
        from ... import numpy as np

        T0 = len(toks)
        total = T0 + max_new_tokens
        last, k, v = self.forward_prefill(
            np.array(onp.asarray([toks], "int32")),
            np.array(onp.asarray([T0], "int32")))
        pad = total - T0
        if pad:
            widths = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
            k, v = np.pad(k, widths), np.pad(v, widths)
        toks.append(self._sample(last[0], temperature))
        for i in range(1, max_new_tokens):
            logits, k, v = self.forward_decode(
                np.array(onp.asarray([toks[-1]], "int32")),
                np.array(onp.asarray([T0 + i - 1], "int32")), k, v)
            toks.append(self._sample(logits[0], temperature))
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
