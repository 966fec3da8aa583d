"""Continuous-batching decode engine (ISSUE 7, v2 in ISSUE 18): paged KV
cache + page allocator, radix prefix cache, speculative multi-token
ticks, the three AOT program families, the scheduler's join/evict/shed
behavior, greedy parity against naive generate, the
zero-steady-state-compile contract, and the warmup-manifest / export
round-trips."""
import json
import threading

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve, telemetry as tm
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo import gpt_tiny
from mxnet_tpu.serve.decode import (DecodeEngine, PageAllocator,
                                    PagedKVCache, RadixPrefixCache,
                                    ShedError, SlotAllocator,
                                    accept_longest_prefix, make_draft)
from mxnet_tpu.serve.decode import cache as kv

VOCAB = 50
MAX_LEN = 64


@pytest.fixture(autouse=True)
def clean_telemetry():
    import mxnet_tpu.random as _rnd

    with _rnd._lock:
        rng_key, rng_pending = _rnd._key, _rnd._pending_seed
    host_state = _rnd.host_rng.get_state()
    tm.disable()
    tm.reset()
    yield
    from mxnet_tpu.context import disable_compilation_cache

    disable_compilation_cache()
    tm.disable()
    tm.reset()
    with _rnd._lock:
        _rnd._key, _rnd._pending_seed = rng_key, rng_pending
    _rnd.host_rng.set_state(host_state)


@pytest.fixture(scope="module")
def net():
    mx.random.seed(11)
    model = gpt_tiny(vocab_size=VOCAB, dropout=0.0, num_layers=2, units=32,
                     num_heads=4, max_length=MAX_LEN)
    model.initialize()
    return model


@pytest.fixture(scope="module")
def warm_engine(net):
    # one warmed engine shared by the read-only tests: warmup compiles
    # O(log B · log T) prefills (x2 with the prefix-join family) + one
    # decode program, which dominates the file's runtime if paid per
    # test. All three v2 features on: every parity test below doubles as
    # a bitwise-equivalence check for paging + prefix + speculation.
    eng = DecodeEngine(net, num_slots=4, max_len=MAX_LEN, max_prompt_len=16,
                       prefill_batch=4, page_tokens=8, speculate_k=4,
                       prefix_cache=True, cache_dir=False)
    eng.warmup()
    yield eng
    eng.close()


def _prompts(n, lo=1, hi=16, seed=0):
    rs = onp.random.RandomState(seed)
    return [[int(t) for t in rs.randint(1, VOCAB, size=rs.randint(lo, hi))]
            for _ in range(n)]


def _naive(net, prompt, max_new):
    out = net.generate(prompt, max_new_tokens=max_new, temperature=0.0,
                       use_cache=False)
    return [int(t) for t in out[len(prompt):]]


# -- slot allocator ----------------------------------------------------------
def test_slot_alloc_free_reuse():
    alloc = SlotAllocator(3)
    sids = [alloc.alloc() for _ in range(3)]
    assert sorted(sids) == [0, 1, 2]
    assert alloc.alloc() is None          # full
    assert alloc.free_count == 0 and alloc.live == {0, 1, 2}
    alloc.free(sids[1])
    assert alloc.free_count == 1
    assert alloc.alloc() == sids[1]       # LIFO reuse of the freed slot
    with pytest.raises(MXNetError, match="double free"):
        alloc.free(7)
    with pytest.raises(MXNetError, match="at least one slot"):
        SlotAllocator(0)


# -- page allocator / paged KV cache ----------------------------------------
def test_page_allocator_alloc_free_reuse_exhaustion():
    alloc = PageAllocator(4)
    got = alloc.alloc(3)
    assert len(got) == 3 and alloc.free_count == 1
    assert alloc.alloc(2) is None          # all-or-nothing: no partial grant
    assert alloc.free_count == 1           # the failed alloc took nothing
    one = alloc.alloc(1)
    assert alloc.alloc(1) is None and alloc.free_count == 0
    alloc.free(one + got[:1])
    assert alloc.free_count == 2 and len(alloc.live) == 2
    again = alloc.alloc(2)
    assert set(again) == set(one + got[:1])   # freed ids come back
    with pytest.raises(MXNetError, match="double free"):
        alloc.free(again[:1] + again[:1])
    with pytest.raises(MXNetError, match="at least one page"):
        PageAllocator(0)
    assert alloc.alloc(0) == []


def test_paged_kv_cache_tables_and_bytes():
    cache = PagedKVCache((6, 2, 4, 5, 8), "float32", num_slots=3,
                         max_len=16)
    assert cache.page_tokens == 8 and cache.pages_per_slot == 2
    assert cache.trash == 6
    assert cache.table.shape == (3, 3)     # W + 1 sentinel column
    assert (cache.table == 6).all()
    assert cache.nbytes == 6 * 2 * 4 * 8 * 5 * 4 * 2
    sid = cache.slots.alloc()
    cache.table[sid, :2] = cache.pages.alloc(2)
    cache.lengths[sid] = 9
    assert cache.pages_live() == 2
    cache.reset_row(sid)
    assert (cache.table[sid] == 6).all() and cache.lengths[sid] == 0
    with pytest.raises(MXNetError, match="pool shape"):
        PagedKVCache((6, 2, 4), num_slots=3, max_len=16)


# -- radix prefix cache ------------------------------------------------------
def test_radix_insert_match_refcounts():
    tree = RadixPrefixCache(page_tokens=4)
    prompt = list(range(10, 21))            # 11 tokens = 2 full pages + 3
    h1, adopted = tree.insert(prompt, {0: 100, 1: 101})
    assert adopted == {0, 1}
    # same prompt again: pages already covered, nothing adopted
    h2, adopted2 = tree.insert(prompt, {0: 200, 1: 201})
    assert adopted2 == set()
    # shared-prefix lookup: full pages inside the shared span only
    m, pages, hm = tree.match(prompt[:9] + [99, 98])
    assert m == 8 and pages == [100, 101]
    # a prompt that IS exactly the cached pages + nothing to prefill must
    # hold one token back for the join program's last-logit select
    m2, pages2, h3 = tree.match(prompt[:8])
    assert m2 == 4 and pages2 == [100]
    # pinned nodes are not evictable until every handle is released
    assert tree.evictable_pages() == 0
    assert tree.evict(2) == []
    for h in (h1, h2, hm, h3):
        tree.release(h)
    assert tree.evictable_pages() == 2
    freed = tree.evict(2)
    assert set(freed) == {100, 101}
    m3, pages3, _ = tree.match(prompt)
    assert m3 == 0 and pages3 == []
    with pytest.raises(MXNetError, match="full page"):
        tree.insert([1, 2, 3], {0: 7})


def test_radix_copy_on_write_divergence():
    """Divergence inside a cached span never remaps the partially-shared
    page: the match stops at the last fully-shared page boundary, so the
    divergent request recomputes (copy-on-write by recompute) its own
    copy into a private page."""
    tree = RadixPrefixCache(page_tokens=4)
    a = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    h, _ = tree.insert(a, {0: 50, 1: 51})
    # diverges at token 6 (inside page 1): only page 0 is reusable
    b = [1, 2, 3, 4, 5, 6, 99, 98, 97]
    m, pages, hb = tree.match(b)
    assert m == 4 and pages == [50]
    # the divergent branch inserts its own page-1 copy; page 0 is shared
    hb2, adopted = tree.insert(b, {0: 60, 1: 61})
    assert adopted == {1}                  # page 0 already covered: kept
    m2, pages2, hc = tree.match(b[:8] + [42])
    assert m2 == 8 and pages2 == [50, 61]
    # LRU eviction only touches refcount-0 leaves; pinned paths survive
    for hx in (h, hb, hb2, hc):
        tree.release(hx)
    st = tree.stats()
    assert st["pages"] == 3 and st["hits"] == 2
    freed = tree.evict(10)                 # drain everything evictable
    assert set(freed) == {50, 51, 61}


# -- speculative accept rule -------------------------------------------------
def test_accept_longest_prefix_edges():
    # K=1 (no draft): always exactly the one verified token
    assert accept_longest_prefix([], [7]) == 1
    # full accept: every draft token matches the argmax chain
    assert accept_longest_prefix([5, 6, 7], [5, 6, 7, 8]) == 4
    # zero draft accepted: first draft token misses
    assert accept_longest_prefix([9, 6, 7], [5, 6, 7, 8]) == 1
    # partial: accept up to the first miss
    assert accept_longest_prefix([5, 6, 9], [5, 6, 7, 8]) == 3


def test_drafts():
    ng = make_draft("ngram")
    # trailing bigram (3, 4) occurred before, followed by 5
    assert ng.propose([3, 4, 5, 9, 3, 4], 1) == [5]
    # chained proposals extend the working context
    assert ng.propose([1, 2, 3, 1, 2], 2) == [3, 1]
    assert ng.propose([7], 3) == [7, 7, 7]  # no history: repeat last
    assert make_draft("last").propose([1, 2, 3], 2) == [3, 3]
    with pytest.raises(MXNetError, match="unknown draft"):
        make_draft("bogus")


# -- greedy parity: engine streams == naive generate ------------------------
def test_engine_greedy_parity_with_naive_generate(net, warm_engine):
    prompts = _prompts(6, seed=3)
    streams = [warm_engine.submit(p, max_new_tokens=8) for p in prompts]
    for p, s in zip(prompts, streams):
        assert s.result(timeout=120) == _naive(net, p, 8)


def test_streaming_tokens_and_callbacks(net, warm_engine):
    prompt = [3, 1, 4, 1, 5]
    seen = []
    stream = warm_engine.submit(prompt, max_new_tokens=6,
                                on_token=seen.append)
    got = list(stream)                    # iterator yields as tokens land
    assert got == stream.result(timeout=60) == seen
    assert got == _naive(net, prompt, 6)
    assert stream.done and not stream.expired


def test_ragged_join_evict_over_ticks(net, warm_engine):
    """Requests of different lengths and budgets join/leave mid-flight;
    freed slots are reused by later arrivals within one engine run."""
    prompts = _prompts(10, lo=1, hi=16, seed=5)
    budgets = [1 + (i % 5) for i in range(10)]     # finish at different ticks
    streams = [warm_engine.submit(p, max_new_tokens=b)
               for p, b in zip(prompts, budgets)]
    for p, b, s in zip(prompts, budgets, streams):
        assert s.result(timeout=120) == _naive(net, p, b)
    st = warm_engine.stats()
    assert st["slots_live"] == 0 and st["pending"] == 0
    assert st["prefills"] >= 3            # 10 requests through <= 4 slots
    assert 0.0 < st["mean_slot_occupancy"] <= 1.0


def test_capacity_truncation(net, warm_engine):
    # prompt 4 + budget 100 cannot fit 64 cache positions: the stream is
    # clipped to the cache budget and flagged, not errored
    stream = warm_engine.submit([1, 2, 3, 4], max_new_tokens=100)
    out = stream.result(timeout=120)
    assert stream.truncated
    assert len(out) == MAX_LEN - 4 + 1


def test_submit_validation(warm_engine):
    with pytest.raises(MXNetError, match="empty prompt"):
        warm_engine.submit([])
    with pytest.raises(MXNetError, match="max_prompt_len"):
        warm_engine.submit(list(range(1, 40)))
    with pytest.raises(MXNetError, match="max_new_tokens"):
        warm_engine.submit([1], max_new_tokens=0)


# -- deadlines + load shedding ----------------------------------------------
def _wait_first_token(stream, timeout=60):
    import time

    deadline = time.perf_counter() + timeout
    while not stream.tokens and time.perf_counter() < deadline:
        time.sleep(0.001)
    assert stream.tokens, "stream never produced a first token"


@pytest.fixture(scope="module")
def slow_engine():
    # deadline semantics need a generation that takes WALL time: a deeper
    # net + 200-token budget gives ~100+ ms per hog, so tens-of-ms
    # deadlines have wide margins on both sides
    mx.random.seed(13)
    model = gpt_tiny(vocab_size=VOCAB, dropout=0.0, num_layers=4, units=64,
                     num_heads=4, max_length=256)
    model.initialize()
    eng = DecodeEngine(model, num_slots=1, max_len=256, max_prompt_len=8,
                       prefill_batch=1, max_queue=2, max_wait_us=0,
                       cache_dir=False)
    eng.warmup()
    yield eng
    eng.close()


def test_queue_depth_shed(slow_engine):
    eng = slow_engine
    # occupy the only slot for ~200 ticks, then fill the queue budget
    first = eng.submit([1, 2], max_new_tokens=200)
    _wait_first_token(first)   # admitted: pending count is queue-only now
    waiting = [eng.submit([3], max_new_tokens=2) for _ in range(2)]
    with pytest.raises(ShedError, match="queue at budget"):
        eng.submit([4], max_new_tokens=2)
    assert first.result(timeout=120)
    for s in waiting:
        s.result(timeout=120)
    st = eng.stats()
    assert st["shed"] == 1 and st["requests"] == 4


def test_pending_deadline_shed_and_live_eviction(slow_engine):
    eng = slow_engine
    shed0 = eng.stats()["shed"]
    # hog: occupies the only slot far longer than the victim's deadline
    hog = eng.submit([1, 2, 3], max_new_tokens=200)
    _wait_first_token(hog)
    victim = eng.submit([5], max_new_tokens=2, deadline_ms=25)
    with pytest.raises(ShedError, match="deadline expired"):
        victim.result(timeout=120)
    assert hog.result(timeout=120)
    assert eng.stats()["shed"] == shed0 + 1

    # live eviction: admitted, then the deadline lapses mid-decode —
    # partial tokens are delivered and the stream is marked expired
    evicted = eng.submit([7, 8], max_new_tokens=200, deadline_ms=40)
    out = evicted.result(timeout=120)
    assert evicted.expired
    assert 0 < len(out) < 200
    assert eng.stats()["evicted"] == 1


def test_close_fails_outstanding_streams(net):
    eng = DecodeEngine(net, num_slots=1, max_len=MAX_LEN, max_prompt_len=8,
                       prefill_batch=1, max_wait_us=0, cache_dir=False)
    eng.warmup()
    stream = eng.submit([1, 2], max_new_tokens=60)
    eng.close()
    with pytest.raises(MXNetError, match="closed"):
        stream.result(timeout=60)
    with pytest.raises(MXNetError, match="closed"):
        eng.submit([1])
    eng.close()  # idempotent


# -- the zero-steady-state-compile contract ---------------------------------
def test_zero_steady_state_compiles_64_ragged_clients(net):
    """64 concurrent ragged-length clients against a warmed engine with
    ALL v2 features on (paged KV, radix prefix sharing, speculative K=4):
    the recompile watchdog stays silent and the serve.* telemetry adds
    up. Half the prompts share an 8-token prefix so the prefix-join
    (prefill_ext) path runs under load too."""
    eng = DecodeEngine(net, num_slots=8, max_len=MAX_LEN, max_prompt_len=16,
                       prefill_batch=4, page_tokens=8, speculate_k=4,
                       prefix_cache=True, max_queue=128, cache_dir=False)
    try:
        tm.enable()
        eng.warmup()
        assert int(tm.metrics()["jit.compiles"]) >= 1
        c0 = tm.metrics()["jit.compiles"]
        r0 = tm.counter("jit.recompiles").value
        prompts = _prompts(64, lo=1, hi=16, seed=9)
        shared = _prompts(1, lo=9, hi=10, seed=77)[0]   # covers one page
        for i in range(0, 64, 2):
            prompts[i] = shared + prompts[i][:7]
        budgets = [1 + (i % 6) for i in range(64)]
        results = {}
        barrier = threading.Barrier(8 + 1)

        def client(cid):
            barrier.wait()
            for r in range(8):
                i = cid * 8 + r
                results[i] = eng.submit(
                    prompts[i], max_new_tokens=budgets[i]).result(timeout=300)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(8)]
        for t in threads:
            t.start()
        barrier.wait()
        for t in threads:
            t.join()
        assert int(tm.metrics()["jit.compiles"] - c0) == 0, \
            "warmed DecodeEngine compiled at steady state"
        assert tm.counter("jit.recompiles").value == r0
        for i in (0, 17, 40, 63):   # spot-check greedy parity under load
            assert results[i] == _naive(net, prompts[i], budgets[i])
        st = eng.stats()
        total = sum(len(results[i]) for i in range(64))
        assert st["tokens"] == total == sum(budgets)
        assert st["completed"] == 64 and st["shed"] == 0
        assert tm.counter("serve.tokens_total").value == total
        assert tm.counter("serve.requests").value == 64
        p50, p99 = (tm.histogram("serve.ttft_ms").percentiles(50, 99))
        assert p50 is not None and p99 >= p50
        assert tm.histogram("serve.tpot_ms").percentiles(50)[0] is not None
        assert st["ttft_ms_p50"] is not None
        assert st["tpot_ms_p99"] >= st["tpot_ms_p50"]
        # v2 surfaces: shared prefixes actually skipped prefill tokens,
        # speculation actually verified drafts, pages stayed bounded
        assert st["prefix_hit_tokens"] > 0
        assert tm.counter("serve.prefix_hit_tokens").value == \
            st["prefix_hit_tokens"]
        assert tm.histogram("serve.spec_accept_len").count > 0
        assert 1.0 <= st["spec_accept_mean"] <= 4.0
        assert 0 <= st["kv_pages_live"] <= st["kv_pages"]
        assert st["page_starved"] == 0     # full reservation: never starves
    finally:
        eng.close()


# -- paged KV integration: prefix sharing, oversubscription, equal bytes ----
def test_prefix_sharing_skips_prefill(net, warm_engine):
    """A later request sharing a >= 1-page prompt prefix joins at the
    page-aligned divergence offset: the shared span is counted as hit
    tokens (its prefill is skipped) and the output stays bitwise equal
    to naive greedy."""
    eng = warm_engine
    base = eng.stats()["prefix_hit_tokens"]
    shared = [5, 9, 2, 8, 7, 3, 6, 4, 1]   # 9 tokens: one full 8-tok page
    a = shared + [11, 12]
    b = shared + [13, 14, 15]
    got_a = eng.submit(a, max_new_tokens=5).result(timeout=120)
    got_b = eng.submit(b, max_new_tokens=5).result(timeout=120)
    assert got_a == _naive(net, a, 5)
    assert got_b == _naive(net, b, 5)
    # b (and possibly a repeat of the shared page) hit at least one page
    assert eng.stats()["prefix_hit_tokens"] >= base + 8
    pc = eng.stats()["prefix_cache"]
    assert pc["hits"] >= 1 and pc["pages"] >= 1


def test_page_pool_oversubscription_sheds_not_crashes(net):
    """kv_pages below the full num_slots * W reservation: pages are
    claimed on demand; a slot the pool cannot serve mid-flight truncates
    (never crashes), and every survivor keeps bitwise greedy parity."""
    eng = DecodeEngine(net, num_slots=4, max_len=MAX_LEN, max_prompt_len=16,
                       prefill_batch=4, page_tokens=8, kv_pages=10,
                       speculate_k=1, prefix_cache=False, cache_dir=False)
    try:
        eng.warmup()
        prompts = _prompts(8, lo=4, hi=16, seed=13)
        streams = [eng.submit(p, max_new_tokens=12) for p in prompts]
        for p, s in zip(prompts, streams):
            got = s.result(timeout=300)
            want = _naive(net, p, 12)
            if s.truncated:
                assert 1 <= len(got) and got == want[:len(got)]
            else:
                assert got == want
        assert eng.healthy
        st = eng.stats()
        assert st["completed"] == 8 and st["kv_pages"] == 10
        assert st["kv_pages_live"] == 0    # all pages back after retire
    finally:
        eng.close()


def test_paged_pool_doubles_slots_at_equal_bytes(net):
    """The paging acceptance gauge: doubling num_slots at a FIXED pool
    leaves mem.kv_cache_bytes unchanged — resident KV bytes now scale
    with the page pool, not with slots * max_len."""
    tm.enable()
    readings = {}
    for slots in (4, 8):
        eng = DecodeEngine(net, num_slots=slots, max_len=MAX_LEN,
                           max_prompt_len=8, prefill_batch=1,
                           page_tokens=8, kv_pages=16, prefix_cache=False,
                           max_wait_us=0, cache_dir=False)
        try:
            eng.warmup()
            eng.submit([1, 2, 3], max_new_tokens=2).result(timeout=120)
            readings[slots] = int(tm.gauge("mem.kv_cache_bytes").value)
            assert eng.stats()["cache_bytes"] == readings[slots]
        finally:
            eng.close()
    assert readings[8] == readings[4]      # 2x slots, equal bytes
    # pool-sized: [16 pages, 2 layers, 4 heads, 8 dim, 8 tok] f32 x k,v
    assert readings[4] == 16 * 2 * 4 * 8 * 8 * 4 * 2


# -- pool writes: in place, exact, and nothing but them pool-shaped ----------
POOL_PAGES = 11      # no other dimension of the toy programs is 11
POOL_P = 8
POOL_W = MAX_LEN // POOL_P


def _random_pools(net, seed=5):
    kp, vp = kv.empty_pools(net.cache_spec(), POOL_PAGES, POOL_P)
    rs = onp.random.RandomState(seed)
    return (rs.standard_normal(kp.shape).astype("float32"),
            rs.standard_normal(vp.shape).astype("float32"))


def _table(rows):
    """Page-table rows of width W+1 from leading page ids; the rest (and
    always column W) is the sentinel."""
    tab = onp.full((len(rows), POOL_W + 1), POOL_PAGES, dtype="int32")
    for r, ids in enumerate(rows):
        tab[r, :len(ids)] = ids
    return tab


def _forward_recording(net, view, tokens):
    """``net``'s one forward over ``view``; returns the updated pools and,
    per layer in order, the (k, v) the model handed ``view.attend``."""
    seen = []
    attend = view.attend

    def recording(layer, q, k, v):
        assert layer == len(seen)
        seen.append((k.asnumpy(), v.asnumpy()))
        return attend(layer, q, k, v)

    view.attend = recording
    net(tokens, cache=view)
    kp, vp = view.state()
    return seen, kp.asnumpy(), vp.asnumpy()


def _assert_only_cells(pool_in, pool_out, cells, what):
    """``cells``: {(page, layer, offset): (H, D) row}. Those cells hold
    their rows exactly; every other cell is bit-identical to the input."""
    expect = pool_in.copy()
    for (page, layer, off), row in cells.items():
        expect[page, layer, :, :, off] = row
    onp.testing.assert_array_equal(pool_out, expect, err_msg=what)


@pytest.mark.parametrize("K", [1, 2])
def test_decode_tick_writes_only_its_rows(net, K):
    H, D = 4, 8
    kp, vp = _random_pools(net)
    # slot 0 sits at offset P-1 (K=2 spills into its next page), slot 1 is
    # inactive (all sentinel), slot 2's second row maps to column W, slot
    # 3 writes mid-page
    table = _table([[3, 5], [], [0, 1, 2, 4, 6, 7, 8, 9], [1, 10]])
    positions = onp.array([POOL_P - 1, 5, MAX_LEN - 1, 10], "int32")
    tokens = onp.arange(1, 4 * K + 1, dtype="int32").reshape(4, K)
    tokens = mx.np.array(tokens)
    seen, kp2, vp2 = _forward_recording(net, kv.TickView(
        tokens, mx.np.array(positions), mx.np.array(table),
        mx.np.array(kp), mx.np.array(vp)), tokens)
    assert len(seen) == 2
    for which, (before, after) in enumerate([(kp, kp2), (vp, vp2)]):
        cells = {}
        for layer in range(2):
            rows = seen[layer][which].reshape(4, K, H, D)
            for s in range(4):
                for k in range(K):
                    pos = int(positions[s]) + k
                    page = int(table[s, min(pos // POOL_P, POOL_W)])
                    if page < POOL_PAGES:
                        cells[(page, layer, pos % POOL_P)] = rows[s, k]
        assert len(cells) == 2 * {1: 3, 2: 5}[K]
        if K == 2:   # slot 0's rows landed in two pages
            assert (3, 0, POOL_P - 1) in cells and (5, 0, 0) in cells
        _assert_only_cells(before, after, cells, "kv"[which])


def _prefill_case():
    # row 0: 12 of 16 tokens, both chunks live; row 1: 5 tokens, chunk 1 is
    # past valid_length; row 2: full, but chunk 0's column is the sentinel
    tokens = onp.random.RandomState(3).randint(1, VOCAB, (3, 16)) \
        .astype("int32")
    valid = onp.array([12, 5, 16], "int32")
    return tokens, valid


def _assert_pages(pool_in, pool_out, pages, what):
    """``pages``: {page: (layers, heads, n, head_dim) k/v of its first n
    positions}. Pages not named are bit-identical to the input. (A page
    of the pool is (layers, heads, head_dim, positions).)"""
    out = pool_out.copy()
    for page, want in pages.items():
        n = want.shape[2]
        onp.testing.assert_array_equal(
            out[page][..., :n], want.transpose(0, 1, 3, 2),
            err_msg=f"{what} page {page}")
        out[page] = pool_in[page]
    onp.testing.assert_array_equal(out, pool_in, err_msg=what)


def _stacked(seen, which, H=4, D=8):
    """The recorded per-layer (B, T, units) k (0) or v (1) as
    (B, layers, heads, T, head_dim)."""
    return onp.stack([kv_[which].reshape(3, 16, H, D).transpose(0, 2, 1, 3)
                      for kv_ in seen], 1)


def test_prefill_scatter_writes_only_live_pages(net):
    kp, vp = _random_pools(net)
    tokens, valid = _prefill_case()
    table = _table([[2, 4], [6, 7], [POOL_PAGES, 8]])
    tokens = mx.np.array(tokens)
    seen, kp2, vp2 = _forward_recording(net, kv.PrefillView(
        tokens, mx.np.array(valid), mx.np.array(table),
        mx.np.array(kp), mx.np.array(vp)), tokens)
    for which, (before, after) in enumerate([(kp, kp2), (vp, vp2)]):
        new = _stacked(seen, which)
        _assert_pages(before, after, {
            2: new[0][:, :, 0:8], 4: new[0][:, :, 8:12],
            6: new[1][:, :, 0:5], 8: new[2][:, :, 8:16]}, "kv"[which])


def test_prefix_join_scatter_lands_at_start(net):
    kp, vp = _random_pools(net)
    tokens, valid = _prefill_case()
    # suffixes from page-aligned starts: pages start//P + j of each row
    start = onp.array([8, 24, 0], "int32")
    table = _table([[0, 2, 4], [1, 3, 5, 6, 7], [POOL_PAGES, 8]])
    tokens = mx.np.array(tokens)
    seen, kp2, vp2 = _forward_recording(net, kv.JoinView(
        tokens, mx.np.array(valid), mx.np.array(start), mx.np.array(table),
        mx.np.array(kp), mx.np.array(vp)), tokens)
    for which, (before, after) in enumerate([(kp, kp2), (vp, vp2)]):
        new = _stacked(seen, which)
        _assert_pages(before, after, {
            2: new[0][:, :, 0:8], 4: new[0][:, :, 8:12],
            6: new[1][:, :, 0:5], 8: new[2][:, :, 8:16]}, "kv"[which])


# -- decode attention read straight from the pool ----------------------------
# Pages of 128 positions (the kernel engages on lane-aligned pages only), the
# rest tiny. Slots: 0 ends on its first page's last cell; 1 starts its second
# page; 2 is ragged, mid-page, with sentinel columns after its pages; 3 is
# inactive (all sentinel); 4 holds one position.
PA_PAGES, PA_LAYERS, PA_D, PA_P, PA_W = 7, 2, 8, 128, 3
PA_TABLE = [[3], [5, 1], [0, 6, 2], [], [4]]
PA_POS = [PA_P - 1, PA_P, 2 * PA_P + 37, 11, 0]


def _paged_case(K, hq=4, hkv=4, seed=7, table=PA_TABLE, pos=PA_POS,
                pages=PA_PAGES, dtype="float32"):
    rs = onp.random.RandomState(seed)
    shape = (pages, PA_LAYERS, hkv, PA_D, PA_P)
    tab = onp.full((len(table), PA_W + 1), pages, dtype="int32")
    for r, ids in enumerate(table):
        tab[r, :len(ids)] = ids
    return tuple(mx.np.array(a).astype(dtype).asnumpy() for a in (
        rs.standard_normal((len(table), K, hq, PA_D)),
        rs.standard_normal(shape), rs.standard_normal(shape))) \
        + (tab, onp.array(pos, "int32"))


def _dense_attention(q, kp, vp, layer, tab, pos):
    """The tick's attention as it was before the paged op: gather every
    column of a slot's row into a view over W*P positions, mask to
    positions <= the query's, softmax, weigh. float64 on the host. By the
    op's contract a position in an unmapped page counts for nothing (slot
    0's second query stands on one), and an inactive slot is zeros."""
    S, K, hq, D = q.shape
    num_pages, W = kp.shape[0], tab.shape[1] - 1
    g = hq // kp.shape[2]
    out = onp.zeros((S, K, hq * D))
    for s in range(S):
        ids = [int(i) for i in tab[s, :W]]
        if ids[0] >= num_pages:
            continue
        pages = [min(i, num_pages - 1) for i in ids]
        mapped = onp.repeat(onp.array(ids) < num_pages, PA_P)
        # (Hkv, D, W*P) -> heads repeated for the group
        kv = [onp.repeat(onp.concatenate(
            [pool[i, layer].astype("float64") for i in pages], -1), g, 0)
            for pool in (kp, vp)]
        for k in range(K):
            n = int(pos[s]) + k + 1
            logit = onp.einsum("hd,hdt->ht", q[s, k].astype("float64"),
                               kv[0][..., :n]) / D ** 0.5
            w = onp.exp(logit - logit.max(-1, keepdims=True)) * mapped[:n]
            w /= w.sum(-1, keepdims=True)
            out[s, k] = onp.einsum("ht,hdt->hd", w, kv[1][..., :n]).ravel()
    return out


def _steer_paged_op(body, monkeypatch):
    """``npx.paged_decode_attention`` through the Pallas kernel in interpret
    mode or through the plain body (the per-op jit forgets its traces
    first, so neither body is served the other's)."""
    from mxnet_tpu.ops.registry import get_op

    get_op("paged_decode_attention")._fn_cache.clear()
    if body == "kernel":
        monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("MXTPU_PALLAS_INTERPRET", raising=False)


def _paged_op(case, layer, body, monkeypatch):
    _steer_paged_op(body, monkeypatch)
    q, kp, vp, tab, pos = case
    return mx.npx.paged_decode_attention(
        mx.np.array(q), mx.np.array(kp), mx.np.array(vp),
        mx.np.array(layer, dtype="int32"), mx.np.array(tab),
        mx.np.array(pos)).asnumpy()


# The tick STORES its rows too. Each case: (K, table rows, positions, then
# ``_paged_case``'s keywords); what a case is for stands in its name. The
# slots of "the_slots_above" are PA_TABLE's: page ends, a page's first cell,
# mid-page, inactive, position 0.
PA_LAST = 8       # a pool of 9 pages: the last one is a live slot's
PA_STORES = {
    "the_slots_above": (1, PA_TABLE, PA_POS, {}),
    # slot 0's row opens page 6, which holds NaN in every cell (it was just
    # mapped: nothing of it is the slot's yet); slot 1's opens its table
    "a_page_just_mapped_holding_nan": (1, [[2, 6], [4]], [PA_P, 0], {}),
    "a_pages_last_cell": (1, [[5], [0, 3]], [PA_P - 1, 2 * PA_P - 1], {}),
    # an idle slot's table row is all sentinel, which the kernel's index map
    # clamps to the pool's last page: that page is slot 1's, being written
    "an_idle_slot_beside_the_pools_last_page": (
        1, [[], [1, PA_LAST], []], [7, PA_P + 5, 0], {"pages": PA_LAST + 1}),
    "a_position_past_the_table": (1, [[0, 1, 2], [3]], [3 * PA_P, 9], {}),
    "four_query_heads_a_kv_head_bfloat16": (
        1, PA_TABLE, PA_POS, {"hq": 8, "hkv": 2, "dtype": "bfloat16"}),
    "a_draft_of_two_rows_written_first": (2, PA_TABLE, PA_POS, {}),
}


@pytest.mark.parametrize("body", ["kernel", "reference"])
@pytest.mark.parametrize("K", [1, 2])
def test_paged_decode_attention_matches_the_dense_view(monkeypatch, K, body):
    case = _paged_case(K)
    for layer in range(PA_LAYERS):
        got = _paged_op(case, layer, body, monkeypatch)
        want = _dense_attention(*case[:3], layer, *case[3:])
        assert got.shape == want.shape
        onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
        assert (got[3] == 0).all(), "an inactive slot returns zeros"


@pytest.mark.parametrize("body", ["kernel", "reference"])
@pytest.mark.parametrize("name", list(PA_STORES))
def test_a_tick_that_stores_its_rows_matches_write_then_dense_view(
        monkeypatch, name, body):
    """``TickView.attend`` of a layer: BOTH pools afterwards are, bit for
    bit and on every page of every layer, what ``_write_rows`` makes of
    the old ones; no page but a live slot's own is touched; the output is
    the dense view's over the written pools. Only a draft's rows (K > 1)
    are written by the view itself."""
    K, table, pos, kw = PA_STORES[name]
    q, kp, vp, tab, pos = _paged_case(K, table=table, pos=pos, **kw)
    if "nan" in name:
        kp[6], vp[6] = onp.nan, onp.nan
    S, dtype = len(table), kp.dtype.name
    rs = onp.random.RandomState(11)
    rows = [mx.np.array(rs.standard_normal((S, K, kp.shape[2] * PA_D)))
            .astype(dtype) for _ in range(2)]
    own = {int(tab[s, pos[s] // PA_P]) for s in range(S)
           if pos[s] // PA_P < PA_W} - {kp.shape[0]}
    written = []
    write_rows = kv._write_rows
    monkeypatch.setattr(
        kv, "_write_rows", lambda *a: written.append(1) or write_rows(*a))
    _steer_paged_op(body, monkeypatch)
    for layer in range(PA_LAYERS):
        view = kv.TickView(
            mx.np.zeros((S, K), dtype="int32"), mx.np.array(pos),
            mx.np.array(tab), mx.np.array(kp), mx.np.array(vp))
        want = [write_rows(pool, kv._layer_id(layer), view._page_id,
                           view._hits, r.reshape(S, K, -1, PA_D)).asnumpy()
                for pool, r in zip((view.k_pool, view.v_pool), rows)]
        del written[:]
        got = view.attend(layer, mx.np.array(q).reshape(S, K, -1), *rows)
        assert len(written) == (0 if K == 1 else 2)
        for pool, new, old in zip((view.k_pool, view.v_pool), want,
                                  (kp, vp)):
            pool = pool.asnumpy()
            onp.testing.assert_array_equal(pool, new)
            differs = (pool != old) & ~(onp.isnan(pool) & onp.isnan(old))
            touched = set(onp.nonzero(differs.any((1, 2, 3, 4)))[0])
            assert touched and (K > 1 or touched <= own), (touched, own)
            assert not differs[:, 1 - layer].any()
        out = got.asnumpy().astype("float64")
        tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" \
            else dict(rtol=2e-2, atol=2e-2)
        onp.testing.assert_allclose(
            out, _dense_attention(q, *want, layer, tab, pos), **tol)


def test_the_op_stores_the_rows_of_a_plain_tick_only():
    """A draft's rows can straddle two pages: the op refuses them by name."""
    q, kp, vp, tab, pos = (mx.np.array(a) for a in _paged_case(2))
    rows = mx.np.zeros((len(PA_TABLE), 2, 4, PA_D))
    with pytest.raises(MXNetError, match="K = 1 tick only"):
        mx.npx.paged_decode_attention(q, kp, vp, mx.np.array(0, dtype="int32"),
                                      tab, pos, k=rows, v=rows)


def test_paged_kernel_is_the_body_under_interpret(monkeypatch):
    """The interpret-mode case above ran the kernel, not the plain body."""
    from mxnet_tpu.ops import pallas_kernels as pk

    called = []
    real = pk._paged_decode_tpu
    monkeypatch.setattr(pk, "_paged_decode_tpu",
                        lambda *a: called.append(1) or real(*a))
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    q, kp, vp, tab, pos = (mx.np.array(a)._data for a in _paged_case(1))
    pk.paged_decode_attention(q, kp, vp, 0, tab, pos)
    assert called
    # pages that are not lane-aligned take the plain body
    del called[:]
    pk.paged_decode_attention(q, kp[..., :8], vp[..., :8], 0, tab, pos // 16)
    assert not called


@pytest.mark.parametrize("body", ["kernel", "reference"])
@pytest.mark.parametrize("K", [1, 2])
def test_nothing_past_a_slots_length_is_read(monkeypatch, K, body):
    """Every cell past a slot's last query and every page no slot holds is
    NaN: the outputs are finite and equal to the clean run's."""
    q, kp, vp, tab, pos = case = _paged_case(K)
    dirty_k, dirty_v = (onp.full_like(kp, onp.nan) for _ in range(2))
    for s, ids in enumerate(PA_TABLE):
        end = int(pos[s]) + K          # positions 0 .. end-1 are the slot's
        for j, page in enumerate(ids):
            n = max(0, min(PA_P, end - j * PA_P))
            dirty_k[page, ..., :n] = kp[page, ..., :n]
            dirty_v[page, ..., :n] = vp[page, ..., :n]
    assert onp.isnan(dirty_k).any()
    for layer in range(PA_LAYERS):
        clean = _paged_op(case, layer, body, monkeypatch)
        got = _paged_op((q, dirty_k, dirty_v, tab, pos), layer, body,
                        monkeypatch)
        assert onp.isfinite(got).all()
        onp.testing.assert_array_equal(got, clean)


def test_paged_kernel_shares_a_page_among_grouped_heads(monkeypatch):
    """Grouped-query attention at g = 8: eight query heads read each KV
    head's page (the kernel indexes the page by ``h // g``)."""
    case = _paged_case(2, hq=16, hkv=2)
    got = _paged_op(case, 1, "kernel", monkeypatch)
    want = _dense_attention(*case[:3], 1, *case[3:])
    onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("speculate_k", [1, 2])
def test_engine_on_the_paged_kernel_equals_uncached_greedy(
        paged_kernel_interpreted, speculate_k):
    """The tick through the Pallas kernel (interpret mode, pages of 128),
    storing its own rows (K = 1) or reading what the view wrote (a draft
    of 2): the served tokens are those of the uncached loop."""
    mx.random.seed(17)
    model = gpt_tiny(vocab_size=VOCAB, dropout=0.0, num_layers=2, units=32,
                     num_heads=4, max_length=256)
    model.initialize()
    rs = onp.random.RandomState(2)
    prompts = [rs.randint(1, VOCAB, n).tolist() for n in (3, 11, 16)]
    want = [model.generate(p, max_new_tokens=7, temperature=0.0,
                           use_cache=False)[len(p):] for p in prompts]
    eng = DecodeEngine(model, num_slots=2, max_len=256,
                       max_prompt_len=16, prefill_batch=1,
                       page_tokens=128, speculate_k=speculate_k,
                       prefix_cache=False, max_wait_us=0,
                       cache_dir=False)
    try:
        streams = [eng.submit(p, max_new_tokens=7) for p in prompts]
        got = [st.result(timeout=300) for st in streams]
    finally:
        eng.close()
    stored = paged_kernel_interpreted
    assert stored and set(stored) == {speculate_k == 1}
    assert got == want


def _hlo_instructions(text):
    """(result shape, opcode, line) of every instruction of an HLO text."""
    import re

    pat = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (\S+) ([\w\-]+)\(")
    return [(m.group(1), m.group(2), ln) for ln in text.splitlines()
            for m in [pat.match(ln)] if m]


@pytest.mark.parametrize("family", ["decode", "prefill", "prefill_ext"])
def test_nothing_but_the_update_is_pool_shaped(net, family):
    """Structural guard on the lowered programs: a one-hot matmul over
    pages, a select over the pool or a copy of it would show here."""
    import re

    from mxnet_tpu.serve.decode import DecodePrograms

    progs = DecodePrograms(net, num_slots=3, max_len=MAX_LEN,
                           prefill_batch=2, max_prompt_len=16,
                           page_tokens=POOL_P, kv_pages=POOL_PAGES,
                           speculate_k=2, prefix_cache=True)
    assert progs.cache_shape == (POOL_PAGES, 2, 4, 8, POOL_P)
    pool = mx.np.zeros(progs.cache_shape)._data
    i32 = lambda *shape: onp.zeros(shape, "int32")  # noqa: E731
    Wt = progs.table_width
    if family == "decode":
        gkey = "decode:2"
        data = [i32(3, 2), i32(3), i32(3, Wt)]
    else:
        gkey = f"{family}:16"
        data = [i32(2, 16), i32(2)] + ([i32(2)] if family == "prefill_ext"
                                       else []) + [i32(2, Wt)]
    args = data + [pool, pool] + [progs._params[n]
                                  for n in progs._graph_params[gkey]]
    text = progs._cops[gkey].lower(*args).as_text(dialect="hlo")
    pool_shape = "f32[%s]" % ",".join(str(d) for d in progs.cache_shape)
    instrs = _hlo_instructions(text)
    # (a ``call`` only wraps one registered op, whose own instructions
    # are listed in the callee)
    shaped = [(op, ln) for shape, op, ln in instrs
              if shape.startswith(pool_shape)
              and op not in ("parameter", "call")]
    assert shaped, "the program no longer updates the pool"
    assert all(op in ("scatter", "dynamic-update-slice")
               for op, _ in shaped), shaped
    dots = [ln for _, op, ln in instrs if op == "dot"]
    assert dots
    assert not [ln for ln in dots
                if re.search(r"[\[,]%d[,\]]" % POOL_PAGES, ln)]


# -- warmup manifest / export round trips -----------------------------------
def test_decode_manifest_roundtrip(net, tmp_path):
    tm.enable()
    mpath = str(tmp_path / "gpt.decode.manifest.json")
    eng = DecodeEngine(net, num_slots=4, max_len=MAX_LEN, max_prompt_len=16,
                       prefill_batch=2,
                       cache_dir=str(tmp_path / "xla_cache"))
    try:
        manifest = eng.warmup(mpath)
        prompt = [2, 7, 1, 8]
        want = eng.submit(prompt, max_new_tokens=5).result(timeout=120)
    finally:
        eng.close()
    m = serve.decode.load_decode_manifest(mpath)
    assert m["kind"] == "decode_engine" and m["num_slots"] == 4
    assert m["len_ladder"] == [8, 16] and m["batch_ladder"] == [1, 2]
    # page_tokens clamps to max_len here, so the pool is one page per
    # slot: same bytes as the old slot-cache reservation
    assert m["page_tokens"] == MAX_LEN and m["kv_pages"] == 4
    assert m["speculate_k"] == 1 and m["prefix_cache"] is True
    assert m["cache_shape"] == [4, 2, 4, 8, MAX_LEN]
    assert m["signatures"] == manifest["signatures"]
    assert set(m["signatures"]) == {
        "decode|1", "prefill|1|8", "prefill|1|16", "prefill|2|8",
        "prefill|2|16", "prefill_ext|1|8", "prefill_ext|1|16",
        "prefill_ext|2|8", "prefill_ext|2|16"}

    # a fresh engine built FROM the manifest adopts its geometry, warms at
    # construction, and serves with zero further compiles
    eng2 = DecodeEngine(net, num_slots=16,  # manifest overrides this
                        manifest=mpath,
                        cache_dir=str(tmp_path / "xla_cache"))
    try:
        assert eng2.num_slots == 4 and eng2.prefill_batch == 2
        c0 = tm.metrics()["jit.compiles"]
        got = eng2.submit(prompt, max_new_tokens=5).result(timeout=120)
        assert got == want
        assert int(tm.metrics()["jit.compiles"] - c0) == 0
    finally:
        eng2.close()

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 99}))
    with pytest.raises(MXNetError, match="decode manifest"):
        serve.decode.load_decode_manifest(str(bad))


def test_manifest_of_the_older_pool_layout_is_refused(tmp_path):
    """Version 2 artefacts hold graphs and a ``cache_shape`` for the pool
    ``[.., page_tokens, head_dim]``: refused by name, never loaded."""
    old = tmp_path / "old-decode.manifest.json"
    old.write_text(json.dumps({
        "kind": "decode_engine", "version": 2, "page_tokens": 8,
        "cache_shape": [4, 2, 4, 8, 8]}))
    with pytest.raises(MXNetError, match="another KV pool layout"):
        serve.decode.load_decode_manifest(str(old))
    with pytest.raises(MXNetError, match="re-exported"):
        DecodeEngine.from_export(str(old))


# -- bench smoke (mirrors test_bench_serve_smoke) ---------------------------
def test_bench_serve_llm_smoke(monkeypatch):
    """bench.py serve_llm (small) with the full v2 stack on — speculative
    K=4, 50% prefix-shared prompts, paged 2x-slots at equal bytes: decodes
    with zero steady-state recompiles and surfaces the v2 counters. Counts
    only: a CPU run yields no time, so no ratio of two is asserted."""
    import bench

    monkeypatch.setenv("BENCH_SERVE_LLM_SMALL", "1")
    monkeypatch.setenv("BENCH_SPECULATE_K", "4")
    monkeypatch.setenv("BENCH_PREFIX_SHARED", "50")
    monkeypatch.setenv("BENCH_PAGED", "1")
    r = bench.bench_serve_llm()
    assert r["unit"] == "tok/s" and r["value"] > 0
    assert r["compiles_steady"] == 0, r
    assert r["shed"] == 0 and r["evicted"] == 0
    assert r["ttft_ms_p99"] >= r["ttft_ms_p50"]
    assert r["speculate_k"] == 4 and 1.0 <= r["spec_accept_mean"] <= 4.0
    assert r["prefix_hit_tokens"] > 0
    assert r["num_slots"] == 8 and r["paged_2x_slots"]


def test_decode_export_roundtrip(net, tmp_path):
    """Export → fresh model-less engine (the SymbolBlock.imports analog):
    the traced graphs + params round-trip through JSON/npz and serve the
    same token streams with zero compiles beyond warmup."""
    prefix = str(tmp_path / "gpt")
    eng = DecodeEngine(net, num_slots=4, max_len=MAX_LEN, max_prompt_len=16,
                       prefill_batch=2, cache_dir=False)
    try:
        mpath = eng.export(prefix)
        prompts = _prompts(4, seed=21)
        want = [eng.submit(p, max_new_tokens=6).result(timeout=120)
                for p in prompts]
    finally:
        eng.close()
    assert mpath.endswith("-decode.manifest.json")

    tm.enable()
    eng2 = DecodeEngine.from_export(prefix, cache_dir=False)
    try:
        c0 = tm.metrics()["jit.compiles"]
        got = [eng2.submit(p, max_new_tokens=6).result(timeout=120)
               for p in prompts]
        assert got == want
        assert int(tm.metrics()["jit.compiles"] - c0) == 0, \
            "re-imported decode engine compiled at steady state"
    finally:
        eng2.close()


# -- one tick in flight (ISSUE 33): speculate_k == 1 ----------------------------
def _plain_engine(net, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("max_prompt_len", 16)
    kw.setdefault("prefill_batch", 2)
    kw.setdefault("page_tokens", 8)
    kw.setdefault("speculate_k", 1)
    kw.setdefault("prefix_cache", False)
    kw.setdefault("cache_dir", False)
    eng = DecodeEngine(net, **kw)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def plain_engine(net):
    eng = _plain_engine(net)
    yield eng
    eng.close()


class _Compiles:
    """Counts what jax builds, as the benchmark's compile log does."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@pytest.fixture(scope="module")
def compiles():
    return _Compiles()


def _ticks_in_flight(eng):
    return sum(f.tick for f in eng._inflight)


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_tokens_with_a_tick_in_flight_are_the_models_greedy_tokens(
        net, prefix_cache, compiles):
    """More requests than slots, budgets from 1 to 9, a shared page-long
    prefix among half of them, arrivals spread over the run: requests are
    admitted, finish and are replaced while ticks are in flight, and each
    gets the model's own greedy continuation token for token. Nothing is
    compiled after warmup()."""
    import time

    eng = _plain_engine(net, prefix_cache=prefix_cache)
    try:
        built = compiles.n
        prompts = _prompts(14, lo=1, hi=16, seed=33)
        shared = _prompts(1, lo=9, hi=10, seed=34)[0]
        for i in range(0, 14, 2):
            prompts[i] = shared + prompts[i][:6]
        budgets = [1 + (5 * i) % 9 for i in range(14)]
        seen = [[] for _ in prompts]
        streams = []
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            streams.append(eng.submit(p, max_new_tokens=b,
                                      on_token=seen[i].append))
            if i % 3 == 2:
                time.sleep(0.01)
        results = [s.result(timeout=120) for s in streams]
        # (the model's own loop below builds programs of its own)
        assert compiles.n == built, "compiled after warmup()"
        for p, b, s, got, out in zip(prompts, budgets, streams, seen,
                                     results):
            assert out == _naive(net, p, b) == got
            assert not s.truncated and not s.expired
        st = eng.stats()
        assert st["completed"] == 14 and st["tokens"] == sum(budgets)
        assert st["slots_live"] == 0 and st["kv_pages_live"] == (
            st["prefix_cache"]["pages"] if prefix_cache else 0)
        assert 0 < st["ticks_overlapped"] <= st["ticks"]
        assert (st["prefix_hit_tokens"] > 0) == prefix_cache
    finally:
        eng.close()


def test_a_long_saturated_run_overlaps_nearly_every_tick(net, plain_engine):
    """Slots full for hundreds of ticks: all but the first tick after an
    idle moment go out while the tick before is unread; stats() after the
    last result and drain() have nothing in flight left to count."""
    eng = plain_engine
    st0 = eng.stats()
    prompts = _prompts(8, lo=2, hi=8, seed=35)
    streams = [eng.submit(p, max_new_tokens=50) for p in prompts]
    assert eng.drain(timeout=300) is True
    assert not eng._inflight and all(s.done for s in streams)
    eng.resume()
    for p, s in zip(prompts, streams):
        assert s.result(timeout=1) == _naive(net, p, 50)
    st = eng.stats()
    ticks = st["ticks"] - st0["ticks"]
    # two waves of four requests: 2 x 49 ticks
    assert ticks >= 98
    assert st["tokens"] - st0["tokens"] == 8 * 50
    assert (st["ticks_overlapped"] - st0["ticks_overlapped"]) / ticks > 0.9
    # per tick, for the slots the tick ran with (as the benchmark reads it)
    occupancy = (st["mean_slot_occupancy"] * st["ticks"]
                 - st0["mean_slot_occupancy"] * st0["ticks"]) / ticks
    assert 0.9 < occupancy <= 1.0


def test_speculation_keeps_nothing_in_flight(net, warm_engine):
    """speculate_k > 1 drafts from the accepted tokens on the host: every
    program is read back before the next, the tokens are what they were."""
    prompts = _prompts(6, seed=36)
    seen = []
    streams = [warm_engine.submit(
        p, max_new_tokens=9,
        on_token=lambda _t: seen.append(len(warm_engine._inflight)))
        for p in prompts]
    for p, s in zip(prompts, streams):
        assert s.result(timeout=120) == _naive(net, p, 9)
    # on_token runs in the commit of the one record there is
    assert set(seen) == {1}
    st = warm_engine.stats()
    assert st["ticks"] > 0 and st["ticks_overlapped"] == 0


def test_a_slot_evicted_with_its_row_in_flight_emits_no_more(net,
                                                             plain_engine):
    """The victim's deadline passes while the commit of tick n runs, that
    is with its row of tick n+1 already dispatched: it finishes with the
    tokens committed so far, the row in flight is dropped, its pages go
    back once, and its neighbour's tokens are untouched."""
    import time

    eng = plain_engine
    st0 = eng.stats()
    a, b = [7, 3, 9, 2], [4, 4, 8, 1, 6]
    seen = []

    def on_token(_tok):
        seen.append(_ticks_in_flight(eng))
        if len(seen) == 3:
            victim.deadline = time.perf_counter() - 1.0

    # the callback runs on the scheduler's thread after submit() returned
    other = eng.submit(b, max_new_tokens=20)
    victim = eng.submit(a, max_new_tokens=40, deadline_ms=600_000,
                        on_token=on_token)
    got = victim.result(timeout=120)
    assert victim.expired and got == _naive(net, a, 40)[:3]
    # token 3 came with tick 2, and tick 3 was out before it was read
    assert seen == [seen[0], 2, 2]
    assert other.result(timeout=120) == _naive(net, b, 20)
    assert eng.drain(timeout=60) and eng.healthy
    eng.resume()
    st = eng.stats()
    assert st["evicted"] - st0["evicted"] == 1
    assert st["tokens"] - st0["tokens"] == 3 + 20
    assert st["kv_pages_live"] == 0 and st["slots_live"] == 0
    # the slot and the pages serve the next request
    assert eng.submit(a, max_new_tokens=6).result(timeout=120) == \
        _naive(net, a, 6)


def test_a_starved_slot_with_a_tick_in_flight_emits_one_more_token(net):
    """Two requests grow in step until the pool has no page for either:
    each commits one more token (as the synchronous engine did), retires
    truncated with no row in the tick after, and frees its pages once."""
    eng = _plain_engine(net, num_slots=2, kv_pages=4)
    try:
        prompts = [[5, 1, 7, 2, 9, 3], [8, 2, 6, 4, 1, 7]]
        streams = [eng.submit(p, max_new_tokens=30) for p in prompts]
        for p, s in zip(prompts, streams):
            got = s.result(timeout=120)
            # first token, positions 6..15 with a page under them, and the
            # one token of the tick that found the pool dry
            assert len(got) == 1 + 10 + 1 and s.truncated
            assert got[:-1] == _naive(net, p, 11)
        assert eng.drain(timeout=60) and eng.healthy
        st = eng.stats()
        assert st["page_starved"] == 2 and st["completed"] == 2
        assert st["kv_pages_live"] == 0 and st["tokens"] == 24
        assert st["ticks"] == 11
    finally:
        eng.close()


@pytest.mark.chaos
@pytest.mark.parametrize("times", [2, 50])
def test_a_fault_at_the_tick_with_a_tick_in_flight(net, times):
    """The fourth tick's dispatch fails while the third is unread. Within
    the retry budget (2) the dispatch is made again and no client sees it;
    past it every stream, the one whose last token was in flight too, ends
    in EngineDeadError with the cause: none hangs."""
    from mxnet_tpu.serve.decode import EngineDeadError
    from mxnet_tpu.testing import chaos

    eng = _plain_engine(net, num_slots=2)
    try:
        chaos.inject("decode.tick", "raise", countdown=3, times=times)
        p, q = [3, 1, 4, 1], [2, 7, 1, 8, 2]
        short = eng.submit(p, max_new_tokens=4)     # ends with tick 3
        long = eng.submit(q, max_new_tokens=12)
        if times <= 2:
            assert short.result(timeout=120) == _naive(net, p, 4)
            assert long.result(timeout=120) == _naive(net, q, 12)
            assert tm.REGISTRY.counter("serve.retries").value == times
            assert eng.healthy and eng.stats()["ticks_overlapped"] >= 9
        else:
            for s in (short, long):
                with pytest.raises(EngineDeadError) as err:
                    s.result(timeout=120)
                assert isinstance(err.value.__cause__, chaos.FaultError)
            assert short.tokens == _naive(net, p, 4)[:3]
            assert not eng.healthy and not eng._inflight
    finally:
        chaos.clear()
        eng.close()


def test_an_engine_from_an_export_overlaps_too(net, tmp_path, compiles):
    prefix = str(tmp_path / "gpt")
    eng = _plain_engine(net)
    try:
        eng.export(prefix)
    finally:
        eng.close()
    eng2 = DecodeEngine.from_export(prefix, cache_dir=False)
    try:
        built = compiles.n
        prompts = _prompts(6, seed=37)
        streams = [eng2.submit(p, max_new_tokens=12) for p in prompts]
        results = [s.result(timeout=120) for s in streams]
        assert compiles.n == built, "compiled after from_export()"
        for p, out in zip(prompts, results):
            assert out == _naive(net, p, 12)
        st = eng2.stats()
        assert st["ticks_overlapped"] / st["ticks"] > 0.8
    finally:
        eng2.close()
