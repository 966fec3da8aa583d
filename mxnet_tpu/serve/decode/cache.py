"""The paged KV cache: the pool's layout, the host's page tables and the
views a model's forward is handed.

This is the one module that knows how a layer's K/V is stored, written
and attended over. A servable model has ONE forward pass: handed a view
(``cache=``), its attention layers call ``view.attend(layer, q, k, v)``
where they would call the attention op, and its embedding takes
``view.positions(limit)``. What the model states in return is what a
cache must hold: ``model.cache_spec()`` -> ``{"layers", "heads",
"head_dim", "dtype"}`` (heads: the KV heads THIS rank holds).

**Layout.** The pool pair has shape ``POOL_AXES`` = ``[pages, layers,
heads, head_dim, page_tokens]``: a shared pool of fixed-size pages, each
``page_tokens`` positions of one layer-stack, in place of a ``[max_len]``
reservation per slot. A page of one layer keeps its positions along the
LAST axis: that is the layout the chip gives the pool anyway (head_dim 64
is half a vector register's lanes, so it made ``page_tokens`` the fastest
axis of the older ``[.., page_tokens, head_dim]`` shape), and declared
so, the decode kernel takes the pool as it stands, a page of a layer
being one contiguous block in which ``q . K`` leaves the positions along
the lanes. A slot's cache is an int32 page-table ROW of width W+1 =
ceil(max_len/page_tokens)+1 mapping logical page index -> pool page id;
the sentinel id ``num_pages`` (one past the pool) marks unmapped columns.

**Reads.** The tick never gathers: ``npx.paged_decode_attention`` walks
the pages a slot's row maps, up to the slot's length, where they lie in
the pool (Pallas kernel ``mxtpu_paged_decode`` on the chip, a gather +
mask + softmax of the same numbers elsewhere). Only the prefix join, with
up to a bucket of queries a row, still gathers the row's first W columns
into a contiguous [W*P] view for the dense masked attention
(``_gather_page_view``; the sentinel clips to a real page whose positions
the mask always excludes).

**Writes** are indexed updates of the (donated) pool, in place and a
WHOLE PAGE of one or all layers at a time: ``np.index_update`` at the
page ids the table maps. (An update of a single position makes XLA lay
the whole pool out anew and back, an update of whole pages does not.) The
tick therefore reads the pages its rows land in, puts the rows in and
writes the pages back. A write routed at the sentinel id (an unmapped
column, an inactive slot, a chunk past ``valid_length``) is out of range
— one past the end, never negative — and jax's ``.at[].set`` drops
out-of-range updates, so it vanishes exactly instead of corrupting a live
page. The tick and the prefix join write each layer's k/v BEFORE that
layer's attention, so the pool already holds the new positions. Nothing
but the updates has the pool's shape, and all three views keep fully
static shapes, preserving the zero-recompile serving contract.

**Host side.** ``PagedKVCache`` holds the device pool pair, the page
tables and the free lists. The decode engine's steady state never
allocates: a request is admitted by claiming a free slot id and pages,
and eviction is returning the ids to the free lists — no device work,
stale cells are masked off by the per-slot length vector until the
page's next tenant overwrites them.
"""
from __future__ import annotations

import numpy as onp

from ... import numpy as np
from ... import numpy_extension as npx
from ...base import MXNetError

__all__ = ["POOL_AXES", "pool_shape", "empty_pools", "PrefillView",
           "JoinView", "TickView", "generate", "SlotAllocator",
           "PageAllocator", "PagedKVCache"]

POOL_AXES = ("pages", "layers", "heads", "head_dim", "page_tokens")


def pool_shape(spec, num_pages, page_tokens):
    """The shape of one pool (``POOL_AXES``) for a model's ``cache_spec()``."""
    size = dict(spec, pages=num_pages, page_tokens=page_tokens)
    return tuple(int(size[a]) for a in POOL_AXES)


def empty_pools(spec, num_pages, page_tokens):
    """Preallocated (k_pool, v_pool) of zeros."""
    shape = pool_shape(spec, num_pages, page_tokens)
    return (np.zeros(shape, dtype=spec["dtype"]),
            np.zeros(shape, dtype=spec["dtype"]))


class SlotAllocator:
    """LIFO free list over ``num_slots`` ids. LIFO (not FIFO) reuse keeps
    the live-slot set dense in recently-touched cache rows."""

    def __init__(self, num_slots):
        if num_slots < 1:
            raise MXNetError(f"need at least one slot, got {num_slots}")
        self.num_slots = int(num_slots)
        self._free = list(range(self.num_slots - 1, -1, -1))
        self._live = set()

    def alloc(self):
        """Claim a slot id, or None when every slot is occupied."""
        if not self._free:
            return None
        sid = self._free.pop()
        self._live.add(sid)
        return sid

    def free(self, sid):
        if sid not in self._live:
            raise MXNetError(f"slot {sid} is not live (double free?)")
        self._live.remove(sid)
        self._free.append(sid)

    @property
    def live(self):
        return frozenset(self._live)

    @property
    def free_count(self):
        return len(self._free)

    def __len__(self):
        return self.num_slots


class PageAllocator:
    """LIFO free list over ``num_pages`` KV-pool page ids.

    ``alloc(n)`` is all-or-nothing: it hands back n page ids or None when
    the pool can't cover the request — the scheduler decides whether to
    evict prefix-cache pages, wait for retirements, or shed. Exhaustion
    is therefore a scheduling outcome, never an exception mid-tick."""

    def __init__(self, num_pages):
        if num_pages < 1:
            raise MXNetError(f"need at least one page, got {num_pages}")
        self.num_pages = int(num_pages)
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._live = set()

    def alloc(self, n=1):
        """Claim ``n`` page ids (all-or-nothing); None when short."""
        if n < 0:
            raise MXNetError(f"cannot alloc {n} pages")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self._live.update(ids)
        return ids

    def free(self, ids):
        for pid in ids:
            if pid not in self._live:
                raise MXNetError(
                    f"page {pid} is not live (double free?)")
            self._live.remove(pid)
            self._free.append(pid)

    @property
    def live(self):
        return frozenset(self._live)

    @property
    def free_count(self):
        return len(self._free)

    def __len__(self):
        return self.num_pages


class PagedKVCache:
    """Device-resident paged KV pool pair + the host page tables.

    The pool pair has shape ``POOL_AXES`` (see the module's notes); a
    slot's cache is one int32 page-table row of width
    ``W+1`` (W = ceil(max_len / page_tokens)) mapping logical page index
    to pool page id. ``trash`` (= num_pages, one past the pool) marks
    unmapped columns: in-program, an indexed update routed there is out
    of range and is dropped, the tick's attention passes over such a
    column, and the prefix join's gather clips to a real page whose
    positions its mask never admits. Column W is
    permanently trash — it absorbs the (clipped) routing of speculative
    writes past the slot's capacity. Memory now scales with live tokens:
    ``nbytes`` at equal capacity shrinks by the pool/reservation ratio,
    and a pool sized below num_slots * W oversubscribes capacity safely
    (admission sheds, ticks starve-retire — never crash).
    """

    def __init__(self, shape, dtype="float32", *, num_slots, max_len):
        import jax.numpy as jnp

        shape = tuple(int(d) for d in shape)
        if len(shape) != len(POOL_AXES):
            raise MXNetError(
                f"paged KV pool shape must be {list(POOL_AXES)}, got {shape}")
        self.num_pages = shape[POOL_AXES.index("pages")]
        self.page_tokens = shape[POOL_AXES.index("page_tokens")]
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.pages_per_slot = -(-self.max_len // self.page_tokens)  # W
        self.trash = self.num_pages
        self.k = jnp.zeros(shape, dtype)
        self.v = jnp.zeros(shape, dtype)
        self.lengths = onp.zeros(self.num_slots, dtype="int32")
        # host page tables, one row per slot; column W stays trash
        self.table = onp.full((self.num_slots, self.pages_per_slot + 1),
                              self.trash, dtype="int32")
        self.slots = SlotAllocator(self.num_slots)
        self.pages = PageAllocator(self.num_pages)

    def rebind(self, k, v):
        self.k, self.v = k, v

    def reset_row(self, sid):
        self.table[sid, :] = self.trash
        self.lengths[sid] = 0

    @property
    def nbytes(self):
        return int(self.k.size * self.k.dtype.itemsize * 2)

    def occupancy(self):
        return len(self.slots.live) / self.num_slots

    def pages_live(self):
        return self.num_pages - self.pages.free_count


# -- device side: the views a forward pass is handed -------------------------
def _layer_id(i):
    """Layer ``i`` as an int32 scalar ARRAY: an index of the pool that
    is an operand, so that one eager program serves every layer when
    these bodies are traced (an int in the key is a program a
    layer). In the compiled graph it is a constant all the same."""
    return np.array(i, dtype="int32")


def _split_heads(x, D):
    """(B, T, heads*D) -> (B, heads, T, D). The head count derives from
    the ACTUAL width so tensor-parallel local slices (units/tp, heads/tp,
    same D) split correctly."""
    T = x.shape[1]
    return np.transpose(
        np.reshape(x, (-1, T, x.shape[-1] // D, D)), (0, 2, 1, 3))


def _update_pool(pool, key, value):
    """``pool.at[key].set(value)`` of whole pages, waited for. In a
    compiled program the update is in place. These bodies also run
    EAGERLY, once, when a program is traced, and there every update
    is a copy of the pool: without the wait the host runs layers
    ahead of the device with a pool-sized buffer in flight for each
    (the trace of a 3 GiB pool pair peaked at 15.1 of a v5e's 15.75
    GiB)."""
    return np.index_update(pool, key, value).wait_to_read()


def _page_chunks(x, J, P):
    """(B, ..., T, D) -> (B*J, ..., D, P): T zero-padded to J pages,
    the page axis moved forward and each page's positions last, as
    the pool keeps them. -1 keeps the graph batch-polymorphic across
    compile-time batch buckets."""
    inner, (T, D) = tuple(x.shape[1:-2]), x.shape[-2:]
    if J * P != T:
        x = np.pad(x, ((0, 0),) * (x.ndim - 2)
                   + ((0, J * P - T), (0, 0)))
    x = np.moveaxis(np.reshape(x, (-1,) + inner + (J, P, D)), -3, 1)
    return np.reshape(np.swapaxes(x, -1, -2), (-1,) + inner + (D, P))


def _scatter_pages(k, v, valid_length, start, page_table, k_pool, v_pool,
                   layer=None):
    """Write prompt k/v into the pool, whole pages at a time, with one
    indexed update per pool: (B, layers, heads, T, head_dim) of every
    layer, or (B, heads, T, head_dim) of layer ``layer`` (a
    ``_layer_id``).

    Chunk j of a row lands in the page its ``page_table`` row maps
    for logical page ``start//P + j``. A chunk past ``valid_length``
    is routed at the sentinel id, like one whose table column holds
    it, and the update drops both. The engine never maps one page to
    two rows of a batch, so no two chunks share a page."""
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
    return (_update_pool(k_pool, key, _page_chunks(k, J, P)),
            _update_pool(v_pool, key, _page_chunks(v, J, P)))


def _gather_page_view(pool, layer, flat_ids, W):
    """Gather page-table rows (W columns each, flattened into
    ``flat_ids``) of layer ``layer`` (a ``_layer_id``) straight from
    the pool (no slice of the layer is made first) into a contiguous
    (rows, W*P, units) kv view; the sentinel clamps to the last page.
    Batch-polymorphic: one traced graph serves every batch bucket, so
    no reshape may bake the row count."""
    H, D, P = pool.shape[2:]
    view = pool[flat_ids, layer]                     # (rows*W, H, D, P)
    view = np.transpose(np.reshape(view, (-1, W, H, D, P)),
                        (0, 1, 4, 2, 3))
    return np.reshape(view, (-1, W * P, H * D))


def _write_rows(pool, layer, page_id, hits, rows):
    """Put ``rows`` (S, K, heads, head_dim) into layer ``layer`` (a
    ``_layer_id``) of the pages ``page_id`` (S*J,): read the pages,
    set row k wherever ``hits[k]`` (S, J, 1, 1, P) says, write them
    back. A page that no row hits goes back as it came."""
    S, K, H, D = rows.shape
    old = pool[page_id, layer]              # (S*J, H, D, P); clamps
    new = np.reshape(old, (S, -1) + tuple(old.shape[1:]))
    for k in range(K):
        new = np.where(hits[k], rows[:, k].reshape(S, 1, H, D, 1), new)
    return _update_pool(pool, (page_id, layer), np.reshape(new, old.shape))


class _PagedView:
    """What a forward pass is handed for one program over the paged pool.

    The three views take their program's operands as they come
    (``tokens`` first, the pool pair last) and offer the model:

    - ``positions(limit)``: the absolute positions of this call's
      tokens, (1 or B, T) int32, none past ``limit - 1`` (the model's
      position table: a draft's or an idle slot's position may lie past
      it), for the position embedding;
    - ``attend(layer, q, k, v)``: layer ``layer``'s attention output
      (B, T, heads*head_dim) for the flat ``q, k, v`` (B, T,
      heads*head_dim) of this call's tokens, having stored ``k, v``;
    - ``state()``: the updated (k_pool, v_pool), once, at the end.
    """

    def __init__(self, page_table, k_pool, v_pool):
        self.page_table = page_table
        self.k_pool, self.v_pool = k_pool, v_pool
        self.heads, self.head_dim, self.page_tokens = k_pool.shape[2:]
        self.W = page_table.shape[1] - 1

    def positions(self, limit):
        return np.minimum(self._pos, limit - 1)

    def state(self):
        return self.k_pool, self.v_pool


class PrefillView(_PagedView):
    """Whole right-padded prompts from position 0 (the
    no-shared-prefix case): the EXACT flash-path compute of the plain
    forward (causal attention on the fresh ``q, k, v``, pad keys past
    ``valid_length`` masked out), so the logits are bitwise the plain
    forward's. Only at the end the k/v of all layers, cut into whole
    pages, lands in the pages ``page_table`` (B, W+1) maps: ONE indexed
    update per pool. K/V past ``valid_length`` inside a live page hold
    garbage no later mask admits."""

    def __init__(self, tokens, valid_length, page_table, k_pool, v_pool):
        super().__init__(page_table, k_pool, v_pool)
        T = tokens.shape[1]
        self.valid_length = valid_length
        self._pos = np.arange(T, dtype="int32").reshape(1, T)
        # (B, 1, 1, T) key-padding mask: rides the fused flash path
        # (segment ids) when combined with causal attention
        self._mask = (self._pos < valid_length.astype("int32")
                      .reshape(-1, 1)).reshape(-1, 1, 1, T)
        self._k = [None] * k_pool.shape[1]
        self._v = [None] * k_pool.shape[1]

    def positions(self, limit):
        return self._pos       # a prompt bucket never passes the table

    def attend(self, layer, q, k, v):
        self._k[layer] = _split_heads(k, self.head_dim)
        self._v[layer] = _split_heads(v, self.head_dim)
        return npx.multihead_attention(q, k, v, mask=self._mask,
                                       num_heads=self.heads, causal=True)

    def state(self):
        return _scatter_pages(
            np.stack(self._k, axis=1), np.stack(self._v, axis=1),
            self.valid_length, None, self.page_table,
            self.k_pool, self.v_pool)


class JoinView(_PagedView):
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
    pages in place.)"""

    def __init__(self, tokens, valid_length, start, page_table, k_pool,
                 v_pool):
        super().__init__(page_table, k_pool, v_pool)
        T = tokens.shape[1]
        WP = self.W * self.page_tokens
        self.valid_length = valid_length
        self.start = start.astype("int32")
        self._pos = self.start.reshape(-1, 1) \
            + np.arange(T, dtype="int32").reshape(1, T)
        ar = np.arange(WP, dtype="int32").reshape(1, 1, WP)
        self._mask = (ar <= self._pos.reshape(-1, T, 1)) \
            .reshape(-1, 1, T, WP)
        self._flat_ids = np.reshape(
            npx.slice_axis(page_table, axis=1, begin=0, end=self.W), (-1,))

    def attend(self, layer, q, k, v):
        lay = _layer_id(layer)
        self.k_pool, self.v_pool = _scatter_pages(
            _split_heads(k, self.head_dim), _split_heads(v, self.head_dim),
            self.valid_length, self.start, self.page_table,
            self.k_pool, self.v_pool, layer=lay)
        viewk = _gather_page_view(self.k_pool, lay, self._flat_ids, self.W)
        viewv = _gather_page_view(self.v_pool, lay, self._flat_ids, self.W)
        return npx.multihead_attention(q, viewk, viewv, mask=self._mask,
                                       num_heads=self.heads, causal=False)


class TickView(_PagedView):
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
    attention output; the engine never reads them)."""

    def __init__(self, tokens, positions, page_table, k_pool, v_pool):
        super().__init__(page_table, k_pool, v_pool)
        S, K = tokens.shape
        P, W = self.page_tokens, self.W
        self.S, self.K = S, K
        self.slot_positions = positions.astype("int32")
        pos2 = self.slot_positions.reshape(-1, 1)
        self._pos = pos2 + np.arange(K, dtype="int32").reshape(1, K)
        # pool write routing (shared by every layer): the J pages a
        # slot's K rows can land in, and for each row the cell it takes
        # (the cell whose position is k past the slot's)
        J = 1 + -(-(K - 1) // P)
        col = pos2 // P + np.arange(J, dtype="int32").reshape(1, J)  # (S, J)
        self._page_id = np.reshape(np.take_along_axis(
            page_table, np.minimum(col, W), axis=1), (-1,))
        past = (col * P - pos2).reshape(S, J, 1, 1, 1) \
            + np.arange(P, dtype="int32").reshape(1, 1, 1, 1, P)
        self._hits = [past == k for k in range(K)]

    def attend(self, layer, q, k, v):
        lay = _layer_id(layer)
        rows = (self.S, self.K, self.heads, self.head_dim)
        self.k_pool = _write_rows(self.k_pool, lay, self._page_id,
                                  self._hits, np.reshape(k, rows))
        self.v_pool = _write_rows(self.v_pool, lay, self._page_id,
                                  self._hits, np.reshape(v, rows))
        return npx.paged_decode_attention(
            np.reshape(q, rows), self.k_pool, self.v_pool, lay,
            self.page_table, self.slot_positions)


def generate(model, tokens, max_new_tokens, pick):
    """One request, eagerly: the degenerate case of the decode engine. A
    private pool sized for the request, the prefill view once, then the
    K=1 tick view a token. ``pick(logits (V,)) -> int`` chooses each
    token. Returns prompt + ``max_new_tokens`` new tokens."""
    toks = list(tokens)
    total = len(toks) + max_new_tokens
    P = min(128, total)
    pages = -(-total // P)
    k_pool, v_pool = empty_pools(model.cache_spec(), pages, P)
    table = np.array(onp.arange(pages + 1, dtype="int32").reshape(1, -1))
    prompt = np.array(onp.asarray([toks], "int32"))
    view = PrefillView(prompt, np.array(onp.asarray([len(toks)], "int32")),
                       table, k_pool, v_pool)
    logits = model(prompt, cache=view)[0, len(toks) - 1]
    while True:
        toks.append(pick(logits))
        if len(toks) == total:
            return toks
        k_pool, v_pool = view.state()
        last = np.array(onp.asarray([toks[-1:]], "int32"))
        view = TickView(last, np.array(onp.asarray([len(toks) - 1], "int32")),
                        table, k_pool, v_pool)
        logits = model(last, cache=view)[0, 0]
