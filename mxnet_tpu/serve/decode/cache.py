"""Slot-paged KV cache: preallocated device residency + free-list reuse.

The decode engine's steady state must never allocate: the KV cache for
every concurrent request lives in TWO preallocated device buffers of shape
``[num_slots, layers, heads, max_len, head_dim]`` (vLLM's paged-KV insight
applied at slot granularity — one "page" per request keeps the fixed-shape
``decode_tick(num_slots)`` program compilable once). A request is admitted
by claiming a free slot id, its prompt's k/v are scattered into that slot
by the prefill program, and eviction is just returning the id to the free
list — no device work, the stale rows are masked off by the per-slot
length vector until the slot's next tenant overwrites them.
"""
from __future__ import annotations

import numpy as onp

from ...base import MXNetError

__all__ = ["SlotAllocator", "KVCache", "PageAllocator", "PagedKVCache"]


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


class KVCache:
    """The device-resident cache pair plus the host-side per-slot length
    vector the scheduler feeds to the decode program every tick.

    ``rebind(k, v)`` swaps in the arrays a donated-buffer program returned
    — under donation the previous pair is dead storage, so holding exactly
    one live generation of the cache is the entire memory contract.
    """

    def __init__(self, shape, dtype="float32"):
        import jax.numpy as jnp

        shape = tuple(int(d) for d in shape)
        if len(shape) != 5:
            raise MXNetError(
                "KV cache shape must be [num_slots, layers, heads, max_len, "
                f"head_dim], got {shape}")
        self.num_slots = shape[0]
        self.max_len = shape[3]
        # raw device arrays (not NDArrays): the engine feeds them straight
        # to AOT executables and rebinds their donated successors
        self.k = jnp.zeros(shape, dtype)
        self.v = jnp.zeros(shape, dtype)
        # host copy: the scheduler reads/writes lengths every tick and the
        # decode program takes them as a tiny int32 operand
        self.lengths = onp.zeros(self.num_slots, dtype="int32")
        self.slots = SlotAllocator(self.num_slots)

    def rebind(self, k, v):
        self.k, self.v = k, v

    @property
    def nbytes(self):
        return int(self.k.size * self.k.dtype.itemsize * 2)

    def occupancy(self):
        return len(self.slots.live) / self.num_slots


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

    The pool pair has shape ``[num_pages, layers, heads, head_dim,
    page_tokens]`` (a page of a layer keeps its positions along the last
    axis, as the chip lays it out and the decode kernel reads it); a
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
        if len(shape) != 5:
            raise MXNetError(
                "paged KV pool shape must be [num_pages, layers, heads, "
                f"head_dim, page_tokens], got {shape}")
        self.num_pages = shape[0]
        self.page_tokens = shape[4]
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
