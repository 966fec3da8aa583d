"""mxnet_tpu.serve.decode — continuous-batching autoregressive decoding.

The LLM leg of the serving story (ISSUE 7, decode engine v2 in ISSUE 18):
a PAGED KV cache — a shared pool of fixed-size pages mapped through
per-slot page tables, and the views of it a model's one forward pass is
handed (``cache=view``: ``view.attend(layer, q, k, v)`` stores and
attends, whatever the program; :mod:`cache`) — three AOT-compiled program
families, that forward over one view each — bucketed ``prefill``,
prefix-join ``prefill_ext`` and fixed-shape ``decode_tick_k``
(:mod:`programs`) — a host-side radix
prefix cache sharing prompt-prefix pages across requests (:mod:`prefix`),
speculative multi-token verification (:mod:`spec`), and a
continuous-batching scheduler with streaming token futures, deadlines,
and load shedding (:mod:`engine`).

Quick start::

    eng = serve.decode.DecodeEngine(model, num_slots=8, speculate_k=4)
    eng.warmup("gpt.decode.manifest.json")   # compile everything up front
    stream = eng.submit(prompt_ids, max_new_tokens=32, deadline_ms=500)
    for tok in stream:                       # tokens as they are decoded
        ...
    stream.result()                          # or block for the full list

See docs/DESIGN.md "Decode engine v2".
"""
from .cache import PageAllocator, PagedKVCache, SlotAllocator
from .engine import DecodeEngine, DecodeStream, EngineDeadError, ShedError
from .prefix import RadixPrefixCache
from .programs import DecodePrograms, load_decode_manifest
from .spec import (LastTokenDraft, NgramDraft, accept_longest_prefix,
                   make_draft)

__all__ = ["DecodeEngine", "DecodeStream", "ShedError", "EngineDeadError",
           "SlotAllocator", "PageAllocator", "PagedKVCache",
           "RadixPrefixCache", "DecodePrograms", "load_decode_manifest",
           "NgramDraft", "LastTokenDraft", "make_draft",
           "accept_longest_prefix"]
