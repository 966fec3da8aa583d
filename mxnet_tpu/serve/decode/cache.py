"""The paged KV cache: the pool's layout, the host's page tables and the
views a model's forward is handed.

This is the one module that knows how a layer's K/V (or its ONE latent
row) is stored, written and attended over. A servable model has ONE
forward pass: handed a view (``cache=``), its attention layers call
``view.attend(layer, q, k, v)`` where they would call the attention op
(``view.attend_latent`` for a latent row), and its embedding takes
``view.positions(limit)``. What the model states in return is what a
cache must hold: ``model.cache_spec()`` -> ``{"layers", "heads",
"head_dim", "dtype"}`` (layers: those that keep K/V pages, numbered in
the pool by the model; heads: the KV heads THIS rank holds), or, for a
latent cache, ``{"layers", "latent", "dtype"}`` (below).

**Two kinds of state.** A model whose layers are not all attention
states besides ``"state"``: ``((shape, dtype), ...)``, the arrays ONE
recurrent layer keeps for ONE sequence (a convolution's tail, a
state-space state), ``"state_layers"``: how many such layers, and
optionally ``"counters"``: names of int32 sums the forward adds to. The
manager then holds, beside the pages, one array ``(num_slots, *shape)``
a recurrent layer and entry (``empty_state``): overwritten whole every
tick, not pageable, claimed and released with the slot, and whatever the
slot's last prefill wrote (a prefill starts from zeros, so no tenant sees
its predecessor's). A recurrent layer calls ``view.recur(layer, step)``
where an attention layer calls ``attend``: ``step(*arrays,
valid_length)`` gets its rows' state (zeros in a prefill, the slots' in a
tick) and returns ``(output, *new arrays)``; a prefill keeps the state AS
OF ``valid_length``, so ``step`` must leave it alone past that. The
programs' donated operands are what the cache names, ``(*pools, *state)``
(``PagedKVCache.operands()``: the K and V pair, or the one latent pool), in
this order, then the counters (not donated: ``stats()`` reads them from
another thread). What recurrent state cannot do yet is refused by name
where the programs are built: the radix prefix cache (the join needs a
snapshot of the state at the prefix's end), ``speculate_k > 1`` (a
rejected draft needs the state rolled back), ``tp > 1`` (state heads are
not sharded), ``export``.

**A third kind: the latent row.** A model with multi-head latent attention
(DeepSeek-V3's, A.X-K1's) states ``"latent": (row width, value width)``
in place of ``heads`` and ``head_dim``: what a position keeps a layer is
ONE headless row ``[c | k_rope]`` (576 numbers where per-head K and V would
be 16,384), and the values are a projection of the row's leading ``value
width`` columns, so there is ONE pool, ``[pages, layers, 1, row width,
page_tokens]``, and no V pool: the operands are ``(latent_pool, *state)``
(``pool_count``, ``PagedKVCache.pools``). Such a layer calls
``view.attend_latent(layer, row, q, k, v, scale=, heads=)``: a prefill
attends EXPANDED (the model's own per-head ``q, k, v`` through the flash
path; ``v`` padded to ``k``'s width) and stores the row; a tick attends
ABSORBED (``q`` already multiplied through ``W_UK``: every head against the
shared row, ``npx.mla_decode_attention``, kernel ``mxtpu_mla_decode``: each
live page read once for keys and values) and gets the still-latent sums
back. What latent rows cannot do yet is refused by name where the programs
are built: the prefix join (``JoinView`` gathers per-head pages), ``tp >
1`` (one headless row has no head axis to shard), ``speculate_k > 1`` and
``export`` (not tried).

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
the whole pool out anew and back, an update of whole pages does not.) A
draft's tick (K > 1) and a latent tick therefore read the pages their rows
land in, put the rows in and write the pages back (``_write_rows``). A
write routed at the sentinel id (an unmapped column, an inactive slot, a
chunk past ``valid_length``) is out of range — one past the end, never
negative — and jax's ``.at[].set`` drops out-of-range updates, so it
vanishes exactly instead of corrupting a live page. They and the prefix
join write each layer's k/v BEFORE that layer's attention, so the pool
already holds the new positions. The PLAIN tick (K = 1) makes no update
of its own: ``npx.paged_decode_attention`` is handed the slots' new rows
and stores them, on the chip inside the kernel, which merges a slot's
row into the page it reads last for that slot and copies that one page
back into the pool it was given (aliased to its output); a slot whose
page there is the sentinel starts no copy. Nothing but the updates and
that op's results has the pool's shape, and all three views keep fully
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
from ...base import MXNetError, dtype_name

__all__ = ["POOL_AXES", "pool_shape", "pool_count", "empty_pools",
           "state_shapes",
           "empty_state", "counter_names", "view_layout", "PrefillView",
           "JoinView", "TickView", "generate", "SlotAllocator",
           "PageAllocator", "PagedKVCache"]

POOL_AXES = ("pages", "layers", "heads", "head_dim", "page_tokens")


def pool_count(spec):
    """How many pools a cache for ``spec`` holds: the K and V pair, or ONE
    latent pool (``spec["latent"]``: values are columns of the same row)."""
    return 1 if "latent" in spec else 2


def pool_shape(spec, num_pages, page_tokens):
    """The shape of one pool (``POOL_AXES``) for a model's ``cache_spec()``;
    a latent pool is headless: one row of ``latent[0]`` numbers a position."""
    size = dict(spec, pages=num_pages, page_tokens=page_tokens)
    if "latent" in spec:
        size.update(heads=1, head_dim=spec["latent"][0])
    return tuple(int(size[a]) for a in POOL_AXES)


def empty_pools(spec, num_pages, page_tokens):
    """Preallocated pools of zeros: (k_pool, v_pool), or (latent_pool,)."""
    shape = pool_shape(spec, num_pages, page_tokens)
    return tuple(np.zeros(shape, dtype=spec["dtype"])
                 for _ in range(pool_count(spec)))


def state_shapes(spec, num_slots):
    """[(shape, dtype)] of the recurrent-state arrays a cache for ``spec``
    holds: ``state_layers`` x the entries of ``spec["state"]``, each with
    ``num_slots`` rows. Empty for a model of attention layers only."""
    return [((int(num_slots),) + tuple(int(d) for d in shape), str(dtype))
            for _ in range(int(spec.get("state_layers", 0)))
            for shape, dtype in spec.get("state", ())]


def counter_names(spec):
    return tuple(spec.get("counters", ()))


def empty_state(spec, num_slots):
    """The operands after the pool pair, as zeros: the recurrent state
    (``state_shapes``) and, where the model counts, the counters."""
    arrays = [np.zeros(shape, dtype=dtype)
              for shape, dtype in state_shapes(spec, num_slots)]
    names = counter_names(spec)
    if names:
        arrays.append(np.zeros((len(names),), dtype="int32"))
    return arrays


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

    def __init__(self, shape, dtype="float32", *, num_slots, max_len,
                 state=(), counters=(), pools=2):
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
        # the K and V pair, or one latent pool (``pool_count``)
        self.pools = tuple(jnp.zeros(shape, dtype) for _ in range(pools))
        # recurrent state: one (num_slots, ...) array a layer and entry;
        # a slot's rows belong to whoever holds the slot
        self.state = tuple(jnp.zeros(sh, dt) for sh, dt in state)
        self.counter_names = tuple(counters)
        self.counters = jnp.zeros((len(counters),), "int32") \
            if counters else None
        self.lengths = onp.zeros(self.num_slots, dtype="int32")
        # host page tables, one row per slot; column W stays trash
        self.table = onp.full((self.num_slots, self.pages_per_slot + 1),
                              self.trash, dtype="int32")
        self.slots = SlotAllocator(self.num_slots)
        self.pages = PageAllocator(self.num_pages)

    @property
    def recurrent(self):
        return bool(self.state)

    @property
    def k(self):
        return self.pools[0]

    @property
    def v(self):
        return self.pools[1]

    def operands(self):
        """What every program takes last and gives back: the pools and the
        recurrent state (donated), then the counters (not donated)."""
        tail = () if self.counters is None else (self.counters,)
        return self.pools + self.state + tail

    def rebind(self, outs):
        """Take a program's outputs after its tokens: the new operands, in
        ``operands()``'s order (whatever follows them, a model's auxiliary
        outputs, is not the cache's)."""
        p = len(self.pools)
        n = p + len(self.state)
        self.pools = tuple(outs[:p])
        self.state = tuple(outs[p:n])
        if self.counters is not None:
            self.counters = outs[n]

    def reset_row(self, sid):
        self.table[sid, :] = self.trash
        self.lengths[sid] = 0

    @property
    def nbytes(self):
        return int(sum(a.size * a.dtype.itemsize for a in self.pools))

    @property
    def state_nbytes(self):
        return int(sum(a.size * a.dtype.itemsize for a in self.state))

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


def _attention(q, k, v, mask, kv_heads, head_dim, causal, scale):
    """Dense attention of flat ``q`` (B, T, heads*head_dim) over flat
    ``k, v`` (B, Tk, kv_heads*head_dim): the query heads derive from
    q's ACTUAL width, so grouped-query models (fewer KV heads) and
    tensor-parallel local slices both split correctly. A model whose
    heads are all alike takes the op exactly as before."""
    heads = q.shape[-1] // head_dim
    return npx.multihead_attention(
        q, k, v, mask=mask, num_heads=heads, causal=causal, scale=scale,
        num_kv_heads=None if heads == kv_heads else kv_heads)


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


def _scatter_pages(xs, valid_length, start, page_table, pools, layer=None):
    """Write prompt k/v (or latent rows) into the pools, whole pages at a
    time, with one indexed update per pool: ``xs[i]`` goes into
    ``pools[i]``, (B, layers, heads, T, head_dim) of every layer, or (B,
    heads, T, head_dim) of layer ``layer`` (a ``_layer_id``).

    Chunk j of a row lands in the page its ``page_table`` row maps
    for logical page ``start//P + j``. A chunk past ``valid_length``
    is routed at the sentinel id, like one whose table column holds
    it, and the update drops both. The engine never maps one page to
    two rows of a batch, so no two chunks share a page."""
    NP_, P = pools[0].shape[0], pools[0].shape[4]
    T = xs[0].shape[-2]
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
    return tuple(_update_pool(pool, key, _page_chunks(x, J, P))
                 for x, pool in zip(xs, pools))


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
    - ``attend_latent(layer, row, q, k=None, v=None, scale=, heads=)``: the same
      for a model whose cache holds ONE latent row a position (``row`` (B,
      T, R), stored in place of k, v). A prefill attends EXPANDED: ``q, k``
      (B, T, heads*d_k) and ``v`` (B, T, heads*d_k too: padded) as the
      model expanded them from the row, through the flash path, (B, T,
      heads*d_k) back. A tick (``decoding``) attends ABSORBED: ``q`` (S, K,
      heads*R) against the rows the pages hold, (S, K, heads*value_dim)
      back, still latent;
    - ``recur(layer, step)``: recurrent layer ``layer``'s output, from
      ``step(*arrays, valid_length) -> (output, *new arrays)``: ``arrays``
      are the layer's state for this call's rows, ``valid_length`` (B,) or
      None says past which position of a row ``step`` must leave the
      state alone; the view keeps the new arrays;
    - ``token_mask()``: (B, T) bool, the tokens of this call that are real
      (not padding, not an idle slot's), and ``count(name, value)``: add
      an int32 scalar to the counter ``name`` of the model's
      ``cache_spec()``; ``decoding`` tells a tick from a prefill;
    - ``state()``: the updated operands, once, at the end: (k_pool,
      v_pool) or the one latent pool, then the recurrent state and the
      counters where the model has them.

    ``operands`` is the pools and what follows them in the operand list
    (``empty_state``); ``per_layer`` says how many of its arrays one
    recurrent layer keeps, ``counters`` names the last one's entries,
    ``latent`` is the spec's (``view_layout``)."""

    decoding = False

    def __init__(self, page_table, operands, per_layer=0, counters=(),
                 latent=None):
        self.page_table = page_table
        # a latent cache has ONE pool: ``latent`` = (row width, how many of
        # the row's leading columns are also the values)
        self.latent = None if latent is None else tuple(latent)
        operands = list(operands)
        self.k_pool = operands.pop(0)
        self.v_pool = operands.pop(0) if latent is None else None
        self.heads, self.head_dim, self.page_tokens = self.k_pool.shape[2:]
        self.W = page_table.shape[1] - 1
        self._per_layer = int(per_layer)
        self._counter_names = tuple(counters)
        extra = operands
        self._counters = extra.pop() if self._counter_names else None
        self._recurrent = extra
        self._counts = {}

    def _pools(self):
        return [self.k_pool] if self.latent else [self.k_pool, self.v_pool]

    def _no_latent(self, what):
        return MXNetError(
            f"{type(self).__name__} cannot serve {what} a latent cache "
            "(cache_spec()['latent'])")

    def positions(self, limit):
        return np.minimum(self._pos, limit - 1)

    def _layer_state(self, layer):
        n = self._per_layer
        return slice(layer * n, (layer + 1) * n)

    def recur(self, layer, step):
        raise MXNetError(
            f"{type(self).__name__} cannot serve a model with recurrent "
            "state")

    def count(self, name, value):
        if name not in self._counter_names:
            raise MXNetError(f"the model's cache_spec() names no counter "
                             f"{name!r} (has: {self._counter_names})")
        value = value.astype("int32")
        self._counts[name] = self._counts[name] + value \
            if name in self._counts else value
        return value

    def state(self):
        out = self._pools() + list(self._recurrent)
        if self._counters is not None:
            zero = self._counters[0] * 0
            out.append(self._counters + np.stack(
                [self._counts.get(n, zero) for n in self._counter_names]))
        return tuple(out)


class PrefillView(_PagedView):
    """Whole right-padded prompts from position 0 (the
    no-shared-prefix case): the EXACT flash-path compute of the plain
    forward (causal attention on the fresh ``q, k, v``, pad keys past
    ``valid_length`` masked out), so the logits are bitwise the plain
    forward's. Only at the end the k/v of all layers, cut into whole
    pages, lands in the pages ``page_table`` (B, W+1) maps: ONE indexed
    update per pool. K/V past ``valid_length`` inside a live page hold
    garbage no later mask admits."""

    def __init__(self, tokens, valid_length, page_table, *operands,
                 slots=None, **layout):
        super().__init__(page_table, operands, **layout)
        k_pool = self.k_pool
        T = tokens.shape[1]
        self.valid_length = valid_length
        self.slots = slots     # (B,) the slot of each row: its state's row
        self._pos = np.arange(T, dtype="int32").reshape(1, T)
        self._real = self._pos < valid_length.astype("int32").reshape(-1, 1)
        # (B, 1, 1, T) key-padding mask: rides the fused flash path
        # (segment ids) when combined with causal attention
        self._mask = self._real.reshape(-1, 1, 1, T)
        self._k = [None] * k_pool.shape[1]
        self._v = [None] * k_pool.shape[1]

    def positions(self, limit):
        return self._pos       # a prompt bucket never passes the table

    def token_mask(self):
        return self._real

    def attend(self, layer, q, k, v, scale=None):
        self._k[layer] = _split_heads(k, self.head_dim)
        self._v[layer] = _split_heads(v, self.head_dim)
        return _attention(q, k, v, self._mask, self.heads, self.head_dim,
                          True, scale)

    def attend_latent(self, layer, row, q, k, v, scale=None, heads=1):
        """Expanded: the flash path on the model's own ``q, k, v`` of
        ``heads`` heads; what is stored is ``row``."""
        self._k[layer] = _split_heads(row, self.head_dim)
        return _attention(q, k, v, self._mask, heads, q.shape[-1] // heads,
                          True, scale)

    def recur(self, layer, step):
        """From zeros (a prompt starts a sequence); the state as of
        ``valid_length`` goes to the rows' slots, a row that holds no
        request names slot ``num_slots``: out of range, dropped."""
        where = self._layer_state(layer)
        # (valid * 0, not zeros of B rows: stays an op ON the input, so the
        # traced graph keeps the batch dim symbolic across buckets)
        rows = self.valid_length * 0
        fresh = [np.reshape(rows, (-1,) + (1,) * (a.ndim - 1)).astype(dt)
                 + np.zeros((1,) + tuple(a.shape[1:]), dtype=dt)
                 for a in self._recurrent[where]
                 for dt in [dtype_name(a.dtype)]]
        out, *new = step(*fresh, self.valid_length)
        self._recurrent[where] = [
            _update_pool(a, (self.slots.astype("int32"),), n)
            for a, n in zip(self._recurrent[where], new)]
        return out

    def state(self):
        new = [np.stack(self._k, axis=1)]
        if not self.latent:
            new.append(np.stack(self._v, axis=1))
        pools = _scatter_pages(new, self.valid_length, None, self.page_table,
                               self._pools())
        self.k_pool = pools[0]
        if not self.latent:
            self.v_pool = pools[1]
        return super().state()


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

    def __init__(self, tokens, valid_length, start, page_table, *operands,
                 **layout):
        super().__init__(page_table, operands, **layout)
        if self.latent:
            raise self._no_latent("a prefix join over")
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
        self._real = np.arange(T, dtype="int32").reshape(1, T) \
            < valid_length.astype("int32").reshape(-1, 1)

    def token_mask(self):
        return self._real

    def attend(self, layer, q, k, v, scale=None):
        lay = _layer_id(layer)
        self.k_pool, self.v_pool = _scatter_pages(
            [_split_heads(k, self.head_dim), _split_heads(v, self.head_dim)],
            self.valid_length, self.start, self.page_table,
            [self.k_pool, self.v_pool], layer=lay)
        viewk = _gather_page_view(self.k_pool, lay, self._flat_ids, self.W)
        viewv = _gather_page_view(self.v_pool, lay, self._flat_ids, self.W)
        return _attention(q, viewk, viewv, self._mask, self.heads,
                          self.head_dim, False, scale)


class TickView(_PagedView):
    """One multi-token decode tick against the paged pool.

    tokens : (S, K) int32 — column 0 is each row's last committed
        token, columns 1..K-1 a draft continuation (K=1: the plain
        single-token tick).
    positions : (S,) int32 — column 0's write position (= current
        length); column i lands at positions + i.
    page_table : (S, W+1) int32 row per slot (sentinel = num_pages).

    Each layer stores its S*K new k/v rows and attends the pool that
    holds them: query i reads positions <= positions + i of the pages
    its slot's row maps. In the plain tick (K = 1)
    ``npx.paged_decode_attention`` does both: handed the rows, it puts
    each into the page it reads last for that slot and hands the pools
    back (on the chip the kernel writes that one page of a pool in
    place; no page is gathered or scattered around it). A draft's K > 1
    rows can straddle two pages: the view writes them first (a
    read-modify-write of the pages they land in, ``_write_rows``, as
    ``attend_latent`` does for latent rows) and the op only reads. A
    row whose page id is the sentinel (an inactive slot, a position
    past the table) is written by neither, and a slot with no mapped
    page attends nothing (its logits are those of a zero attention
    output; the engine never reads them)."""

    decoding = True

    def __init__(self, tokens, positions, page_table, *operands, **layout):
        super().__init__(page_table, operands, **layout)
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

    def token_mask(self):
        """A slot is live where its table row maps a first page."""
        live = npx.slice_axis(self.page_table, axis=1, begin=0, end=1) \
            < self.k_pool.shape[0]
        return np.broadcast_to(live, (self.S, self.K))

    def attend(self, layer, q, k, v, scale=None):
        lay = _layer_id(layer)
        rows = (self.S, self.K, self.heads, self.head_dim)
        q = np.reshape(q, (self.S, self.K, -1, self.head_dim))
        k, v = np.reshape(k, rows), np.reshape(v, rows)
        if self.K == 1:
            out, k_pool, v_pool = npx.paged_decode_attention(
                q, self.k_pool, self.v_pool, lay, self.page_table,
                self.slot_positions, scale=scale, k=k, v=v)
            # waited for, as ``_update_pool`` waits: in the eager trace
            # each pool the op returns is a copy
            self.k_pool = k_pool.wait_to_read()
            self.v_pool = v_pool.wait_to_read()
            return out
        self.k_pool = _write_rows(self.k_pool, lay, self._page_id,
                                  self._hits, k)
        self.v_pool = _write_rows(self.v_pool, lay, self._page_id,
                                  self._hits, v)
        return npx.paged_decode_attention(
            q, self.k_pool, self.v_pool, lay, self.page_table,
            self.slot_positions, scale=scale)

    def attend_latent(self, layer, row, q, k=None, v=None, scale=None,
                      heads=1):
        """Absorbed: the S*K new rows go into the pool first, then every
        head's query reads the pages in place (``npx.mla_decode_attention``,
        each live page once for keys and values)."""
        lay = _layer_id(layer)
        width, value_dim = self.latent[:2]
        self.k_pool = _write_rows(
            self.k_pool, lay, self._page_id, self._hits,
            np.reshape(row, (self.S, self.K, 1, width)))
        return npx.mla_decode_attention(
            np.reshape(q, (self.S, self.K, -1, width)), self.k_pool, lay,
            self.page_table, self.slot_positions, value_dim, scale)

    def recur(self, layer, step):
        """Every slot's state in, every slot's state out (an idle slot's
        rows hold whatever: its next prefill overwrites them). One
        position a tick: a draft of K > 1 would need the state rolled
        back where it is rejected."""
        if self.K != 1:
            raise MXNetError("a tick of K > 1 tokens cannot advance "
                             "recurrent state (no rollback of a rejected "
                             "draft)")
        where = self._layer_state(layer)
        out, *new = step(*self._recurrent[where], None)
        # waited for, as ``_update_pool`` is: in the eager trace the host
        # would else run layers ahead with a state-sized buffer each
        self._recurrent[where] = [n.wait_to_read() for n in new]
        return out


def view_layout(spec):
    """The keywords every view takes besides its operands, from a model's
    ``cache_spec()``."""
    return {"per_layer": len(spec.get("state", ())),
            "counters": counter_names(spec), "latent": spec.get("latent")}


def generate(model, tokens, max_new_tokens, pick):
    """One request, eagerly: the degenerate case of the decode engine. A
    private pool sized for the request (and one slot of recurrent state,
    where the model has any), the prefill view once, then the K=1 tick
    view a token. ``pick(logits (V,)) -> int`` chooses each token. Returns
    prompt + ``max_new_tokens`` new tokens."""
    toks = list(tokens)
    total = len(toks) + max_new_tokens
    P = min(128, total)
    pages = -(-total // P)
    spec = model.cache_spec()
    layout = view_layout(spec)
    operands = empty_pools(spec, pages, P) + tuple(empty_state(spec, 1))
    table = np.array(onp.arange(pages + 1, dtype="int32").reshape(1, -1))
    prompt = np.array(onp.asarray([toks], "int32"))
    view = PrefillView(prompt, np.array(onp.asarray([len(toks)], "int32")),
                       table, *operands, slots=np.zeros((1,), dtype="int32"),
                       **layout)
    logits = model(prompt, cache=view)[0, len(toks) - 1]
    while True:
        toks.append(pick(logits))
        if len(toks) == total:
            return toks
        operands = view.state()
        last = np.array(onp.asarray([toks[-1:]], "int32"))
        view = TickView(last, np.array(onp.asarray([len(toks) - 1], "int32")),
                        table, *operands, **layout)
        logits = model(last, cache=view)[0, 0]
