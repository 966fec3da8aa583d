"""Inference fast path (ISSUE 4): shape-bucketed dynamic batcher,
AOT-compiled bucket programs, warmup manifest / export round-trip, the
and the zero-steady-state-recompile contract."""
import json
import os
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serve, telemetry as tm
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.serve import bucket_ladder, pick_bucket, split_sizes
from mxnet_tpu.serve.bucketing import padded_rows

FEAT = 6


@pytest.fixture(autouse=True)
def clean_telemetry():
    # snapshot the global PRNG: _make_net reseeds it, and unseeded tests
    # later in the suite (e.g. ssd loss-decrease) depend on the draw
    # sequence they'd see if this file never ran
    import mxnet_tpu.random as _rnd

    with _rnd._lock:
        rng_key, rng_pending = _rnd._key, _rnd._pending_seed
    host_state = _rnd.host_rng.get_state()
    tm.disable()
    tm.reset()
    yield
    # persistence is process-global jax config once enabled — switch it
    # back off so later compile-heavy tests don't pay disk writes
    from mxnet_tpu.context import disable_compilation_cache

    disable_compilation_cache()
    tm.disable()
    tm.reset()
    with _rnd._lock:
        _rnd._key, _rnd._pending_seed = rng_key, rng_pending
    _rnd.host_rng.set_state(host_state)


def _make_net(hybrid=True, seed=5):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize()
    if hybrid:
        net.hybridize()
    return net


def _predictor(net, **kw):
    # cache_dir=False everywhere persistence is not the thing under test:
    # the on-disk cache tests cover it explicitly with a tmp_path dir
    kw.setdefault("cache_dir", False)
    return net.predictor(example=mx.nd.array(_rows(2)), **kw)


def _rows(n, seed=0, feat=FEAT):
    return onp.random.RandomState(seed).standard_normal(
        (n, feat)).astype("float32")


# -- bucketing --------------------------------------------------------------
def test_bucket_ladder_shapes():
    assert bucket_ladder(64) == [1, 2, 4, 8, 16, 32, 64]
    assert bucket_ladder(1) == [1]
    assert bucket_ladder(48, min_bucket=4) == [4, 8, 16, 32, 48]
    assert bucket_ladder(7) == [1, 2, 4, 7]  # non-power cap always included
    with pytest.raises(MXNetError):
        bucket_ladder(0)
    with pytest.raises(MXNetError):
        bucket_ladder(4, min_bucket=8)


def test_pick_bucket_and_split_sizes():
    ladder = bucket_ladder(32)
    assert pick_bucket(1, ladder) == 1
    assert pick_bucket(5, ladder) == 8
    assert pick_bucket(32, ladder) == 32
    assert pick_bucket(33, ladder) is None  # caller must split first
    assert split_sizes(70, 32) == [32, 32, 6]
    assert split_sizes(1, 32) == [1]
    assert split_sizes(32, 32) == [32]
    with pytest.raises(MXNetError):
        split_sizes(0, 32)
    assert padded_rows(5, 8) == 3


# -- predict: correctness across the ladder ---------------------------------
def test_predict_matches_eager_all_sizes():
    net = _make_net()
    x_ex = mx.nd.array(_rows(2))
    pred = net.predictor(example=x_ex, max_batch=8, cache_dir=False)
    try:
        # n covers: batch of 1, interior bucket, ragged padding, exact
        # max_batch, and a > max_batch batch that must split (8 + 3)
        for n in (1, 3, 5, 8, 11):
            x = mx.nd.array(_rows(n, seed=n))
            want = net(x).asnumpy()
            got = pred.predict(x).asnumpy()
            assert got.shape == want.shape  # unpadded back to exactly n
            onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        assert set(pred.stats()["programs"]) <= set(pred.buckets)
    finally:
        pred.close()


def test_predict_input_validation():
    net = _make_net()
    pred = _predictor(net, max_batch=4)
    try:
        with pytest.raises(MXNetError, match="dtype mismatch"):
            pred.predict(mx.nd.array(_rows(2).astype("int32")))
        with pytest.raises(MXNetError, match="item shape mismatch"):
            pred.predict(mx.nd.array(_rows(2, feat=FEAT + 1)))
        with pytest.raises(MXNetError, match="1 inputs"):
            pred.predict((mx.nd.array(_rows(2)), mx.nd.array(_rows(2))))
        with pytest.raises(MXNetError, match="empty batch"):
            pred.predict(mx.nd.array(onp.zeros((0, FEAT), "float32")))
    finally:
        pred.close()


def test_predictor_rejects_plain_block():
    net = nn.Sequential()  # no hybrid graph to trace
    net.add(nn.Dense(3))
    net.initialize()
    with pytest.raises(MXNetError, match="hybridizable"):
        serve.Predictor(net, mx.nd.array(_rows(2)), max_batch=4,
                        cache_dir=False)


def test_bad_bucket_ladder_rejected():
    net = _make_net()
    with pytest.raises(MXNetError, match="ladder"):
        _predictor(net, max_batch=8, buckets=[1, 2, 4])  # does not reach max_batch


# -- submit: dynamic batching -----------------------------------------------
def test_submit_resolves_futures_correctly():
    net = _make_net()
    pred = _predictor(net, max_batch=8, max_wait_us=500)
    try:
        items = _rows(12, seed=3)
        want = net(mx.nd.array(items)).asnumpy()
        futs = [pred.submit(items[i]) for i in range(len(items))]
        for i, f in enumerate(futs):
            onp.testing.assert_allclose(f.result(timeout=60), want[i],
                                        rtol=2e-5, atol=2e-5)
        with pytest.raises(MXNetError, match="use predict"):
            pred.submit(items)  # whole batch through the single-item API
        with pytest.raises(MXNetError, match="dtype mismatch"):
            pred.submit(items[0].astype("int32"))
    finally:
        pred.close()


def test_dynamic_batching_coalesces_concurrent_submits():
    net = _make_net()
    pred = _predictor(net, max_batch=16, max_wait_us=20_000)
    try:
        pred.warmup()
        items = _rows(48, seed=7)
        want = net(mx.nd.array(items)).asnumpy()
        barrier = threading.Barrier(8 + 1)
        results = {}

        def client(cid):
            barrier.wait()
            for r in range(6):
                i = cid * 6 + r
                results[i] = pred.submit(items[i]).result(timeout=60)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(8)]
        for t in threads:
            t.start()
        barrier.wait()
        for t in threads:
            t.join()
        for i in range(48):
            onp.testing.assert_allclose(results[i], want[i],
                                        rtol=2e-5, atol=2e-5)
        st = pred.stats()
        assert st["requests"] == 48
        assert st["batches"] < 48, \
            "dispatcher never coalesced concurrent requests"
        assert st["batched_rows"] == 48
        assert 0.0 <= st["padding_waste"] < 1.0
        assert st["latency_ms_p50"] is not None
        assert st["latency_ms_p99"] >= st["latency_ms_p50"]
    finally:
        pred.close()


def test_close_is_idempotent_and_rejects_traffic():
    net = _make_net()
    pred = _predictor(net, max_batch=4)
    f = pred.submit(_rows(1)[0])
    f.result(timeout=60)
    pred.close()
    pred.close()
    with pytest.raises(MXNetError, match="closed"):
        pred.submit(_rows(1)[0])
    with pytest.raises(MXNetError, match="closed"):
        pred.predict(mx.nd.array(_rows(2)))


# -- the zero-steady-state-recompile contract -------------------------------
def test_zero_recompiles_after_warmup():
    tm.enable()
    net = _make_net()
    pred = _predictor(net, max_batch=8)
    try:
        pred.warmup()
        warm = int(tm.metrics()["jit.compiles"])
        assert warm >= 1  # warmup itself traced/compiled the ladder
        c0 = tm.metrics()["jit.compiles"]
        r0 = tm.counter("jit.recompiles").value  # warmup's per-bucket
        # traces legitimately count as same-site recompiles; steady state
        # must add none
        for n in (1, 2, 3, 5, 8, 11, 19):   # every bucket + splits
            pred.predict(mx.nd.array(_rows(n, seed=n)))
        futs = [pred.submit(_rows(1, seed=90 + i)[0]) for i in range(10)]
        for f in futs:
            f.result(timeout=60)
        assert int(tm.metrics()["jit.compiles"] - c0) == 0, \
            "warmed Predictor traced a new program at steady state"
        assert tm.counter("jit.recompiles").value == r0
        assert tm.counter("serve.batches").value >= 1
        assert tm.counter("serve.requests").value == 7 + 10
    finally:
        pred.close()


# -- warmup manifest / persistent-cache round trip --------------------------
def test_warmup_manifest_roundtrip(tmp_path):
    tm.enable()
    net = _make_net()
    mpath = str(tmp_path / "model.warmup.json")
    pred = net.predictor(example=mx.nd.array(_rows(2)), max_batch=8,
                         cache_dir=str(tmp_path / "xla_cache"))
    try:
        manifest = pred.warmup(mpath)
        x = mx.nd.array(_rows(3, seed=1))
        want = pred.predict(x).asnumpy()
    finally:
        pred.close()
    m = serve.load_manifest(mpath)
    assert m["version"] == 1
    assert m["max_batch"] == 8 and m["buckets"] == [1, 2, 4, 8]
    assert m["inputs"] == [{"item_shape": [FEAT], "dtype": "float32"}]
    assert set(m["signatures"]) == {"1", "2", "4", "8"}
    assert m["signatures"] == manifest["signatures"]

    # a new Predictor built FROM the manifest warms every bucket at
    # construction and then serves all shapes with zero further compiles
    pred2 = serve.Predictor(net, max_batch=3,  # manifest overrides this
                            manifest=mpath,
                            cache_dir=str(tmp_path / "xla_cache"))
    try:
        assert pred2.max_batch == 8 and pred2.buckets == [1, 2, 4, 8]
        assert pred2.stats()["programs"] == [1, 2, 4, 8]
        c0 = tm.metrics()["jit.compiles"]
        onp.testing.assert_allclose(pred2.predict(x).asnumpy(), want,
                                    rtol=1e-6, atol=1e-6)
        assert int(tm.metrics()["jit.compiles"] - c0) == 0
    finally:
        pred2.close()

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 99}))
    with pytest.raises(MXNetError, match="manifest version"):
        serve.load_manifest(str(bad))


def test_export_import_predictor_roundtrip(tmp_path):
    """Exported hybridized model drives a Predictor in a fresh (simulated)
    session — SymbolBlock.imports + the warmup manifest — without
    retracing beyond the warmed buckets."""
    from mxnet_tpu.gluon.block import SymbolBlock

    net = _make_net()
    x = mx.nd.array(_rows(4, seed=2))
    want = net(x).asnumpy()
    sym_f, par_f = net.export(str(tmp_path / "model"))
    mpath = str(tmp_path / "model.warmup.json")
    pred = net.predictor(example=x, max_batch=8,
                         cache_dir=str(tmp_path / "xla_cache"))
    try:
        pred.warmup(mpath)
    finally:
        pred.close()

    blk = SymbolBlock.imports(sym_f, ["data0"], par_f)
    tm.enable()
    pred2 = blk.predictor(manifest=mpath,
                          cache_dir=str(tmp_path / "xla_cache"))
    try:
        c0 = tm.metrics()["jit.compiles"]
        for n in (1, 3, 4, 8):
            got = pred2.predict(mx.nd.array(_rows(n, seed=2))).asnumpy()
            assert got.shape == (n, 3)
        onp.testing.assert_allclose(
            pred2.predict(x).asnumpy(), want, rtol=2e-5, atol=2e-5)
        f = pred2.submit(onp.asarray(x.asnumpy()[0]))
        onp.testing.assert_allclose(f.result(timeout=60), want[0],
                                    rtol=2e-5, atol=2e-5)
        assert int(tm.metrics()["jit.compiles"] - c0) == 0, \
            "re-imported Predictor retraced beyond the warmed buckets"
    finally:
        pred2.close()


def test_warmup_populates_compilation_cache(tmp_path, monkeypatch):
    # a cache placed from outside wins over cache_dir= (tests/
    # test_device_selection.py); here the argument must be the one in use
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    net = _make_net()
    cache = str(tmp_path / "xla")
    pred = net.predictor(example=mx.nd.array(_rows(2)), max_batch=2,
                         cache_dir=cache)
    try:
        pred.warmup()
    finally:
        pred.close()
    assert pred.cache_dir == cache
    # warmup's AOT compiles must land in the persistent on-disk cache
    assert any(os.scandir(cache)), "persistent compilation cache is empty"


# -- bench smoke (mirrors test_telemetry_overhead_under_budget) -------------
def test_bench_serve_smoke(monkeypatch):
    """bench.py serve (small): the batched fast path serves at steady state
    with zero recompiles and fewer dispatches than requests. Counts only: a
    CPU run yields no time, so no ratio of two is asserted."""
    import bench

    monkeypatch.setenv("BENCH_SERVE_SMALL", "1")
    r = bench.bench_serve()
    assert r["unit"] == "req/s" and r["value"] > 0
    assert r["compiles_steady"] == 0, r
    assert r["dispatches"] <= r["requests"]
