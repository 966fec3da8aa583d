"""DataLoader worker-model micro-benchmark: serial vs threads vs processes.

The per-sample work simulates a decode/augment pipeline that holds the GIL
(byte-level python work + small numpy ops) — the workload class the
reference forks processes for (python/mxnet/gluon/data/dataloader.py).
Spawned process workers should beat thread workers decisively here; thread
workers only win when per-sample work is pure GIL-releasing numpy.

Run:  python benchmark/dataloader_bench.py
Writes benchmark/dataloader_results.json.
"""
import json
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
    import jax

    jax.config.update("jax_platforms", "cpu")

from mxnet_tpu import gluon  # noqa: E402
from mxnet_tpu.gluon.data import DataLoader  # noqa: E402


def decode_heavy(seed):
    """GIL-bound fake decode (~1.5ms, the cost class of a small JPEG):
    python-level byte loop + huffman-ish table lookups hold the GIL."""
    rng = onp.random.RandomState(int(seed))
    raw = rng.bytes(48 * 48 * 3)
    table = list(range(256))
    acc = 0
    for b in raw:  # python-level loop: the GIL-bound part of a decoder
        acc = (acc * 31 + table[b]) & 0xFFFFFFFF
        table[b & 0xFF] = (table[b] + 1) & 0xFF
    img = onp.frombuffer(raw, onp.uint8).reshape(48, 48, 3)
    img = img.astype("float32") / 255.0
    img[0, 0, 0] += (acc % 7) * 1e-9  # keep the loop honest
    return img


def run(loader, batches):
    t0 = time.time()
    n = 0
    for x in loader:
        n += x.shape[0]
        if n >= batches * 64:
            break
    return n / (time.time() - t0)


# ---------------------------------------------------------------------------
# transport-level throughput: bytes/s through the worker->parent channel,
# decode cost excluded. Meaningful on ONE core — it measures copy/IPC
# bandwidth, not parallel speedup: shm moves a batch with two memcpys while
# a pickled queue serializes it through a 64 KiB pipe.
# ---------------------------------------------------------------------------
_T_SHAPE = (4 * 1024 * 1024,)  # 16 MiB float32 per batch
_T_ITERS = 12
_T_NBYTES = 16 * 1024 * 1024


def _shm_sender(q):
    from mxnet_tpu.gluon.data.dataloader import _to_shm

    arr = onp.ones(_T_SHAPE, "float32")
    for _ in range(_T_ITERS):
        segments = []
        q.put(_to_shm(arr, segments))
        for s in segments:
            s.close()


def _pickle_sender(q):
    arr = onp.ones(_T_SHAPE, "float32")
    for _ in range(_T_ITERS):
        q.put(arr)


def _recv_shm(q):
    # symmetric endpoint work: both receivers end with an OWNED host array
    # (unpickling already materializes one on the queue path, so the shm
    # path maps the segment and pays exactly one memcpy — device placement
    # is deliberately excluded from both sides: it is not transport)
    from multiprocessing import shared_memory

    _tag, name, shape, dtype = q.get(timeout=120)
    shm = shared_memory.SharedMemory(name=name)
    onp.array(onp.ndarray(shape, onp.dtype(dtype), buffer=shm.buf))
    shm.close()
    shm.unlink()


def _recv_pickle(q):
    q.get(timeout=120)  # unpickle materializes the owned host array


def _transport_bps(sender, recv):
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    q = ctx.Queue(maxsize=2)
    p = ctx.Process(target=sender, args=(q,), daemon=True)
    # children inherit the env at exec time: pin them to CPU BEFORE they
    # re-import this module (same hazard DataLoader._ensure_pool guards —
    # an unpinned child would race the parent for the TPU runtime)
    from mxnet_tpu.context import spawn_cpu_pinned_env

    with spawn_cpu_pinned_env():
        p.start()
    recv(q)  # first batch excluded: absorbs spawn + import warmup
    t0 = time.perf_counter()
    for _ in range(_T_ITERS - 1):
        recv(q)
    dt = time.perf_counter() - t0
    p.join(timeout=10)
    return (_T_ITERS - 1) * _T_NBYTES / dt


def bench_transport():
    """Returns {shm_bytes_per_sec, pickle_queue_bytes_per_sec, ratio}."""
    shm = _transport_bps(_shm_sender, _recv_shm)
    pkl = _transport_bps(_pickle_sender, _recv_pickle)
    return {"shm_MBps": round(shm / 1e6, 1),
            "pickle_queue_MBps": round(pkl / 1e6, 1),
            "shm_over_pickle": round(shm / pkl, 2),
            "batch_MiB": _T_NBYTES // (1024 * 1024)}


def main():
    n = 512
    ds = gluon.data.SimpleDataset(
        onp.arange(n, dtype="float32")).transform(decode_heavy)
    nb = n // 64
    results = {}
    serial = DataLoader(ds, batch_size=64)
    results["serial"] = run(serial, nb)
    threads = DataLoader(ds, batch_size=64, num_workers=4, thread_pool=True)
    results["threads_4"] = run(threads, nb)
    procs = DataLoader(ds, batch_size=64, num_workers=4)
    for _ in procs:  # absorb spawn+import warmup in a full epoch
        pass
    results["processes_4"] = run(procs, nb)
    results["unit"] = "samples/sec"
    results["process_vs_thread"] = results["processes_4"] / \
        results["threads_4"]
    results["transport"] = bench_transport()
    results["cores"] = len(os.sched_getaffinity(0)) \
        if hasattr(os, "sched_getaffinity") else os.cpu_count()
    if results["cores"] == 1:
        results["note"] = ("single-core host: GIL-bound decode cannot "
                           "parallelize under ANY worker model; process "
                           "workers pay transport overhead with no "
                           "compute win. Re-run on a multi-core host for "
                           "the representative comparison.")
    out = os.path.join(os.path.dirname(__file__),
                       "dataloader_results.json")
    with open(out, "w") as f:
        json.dump({k: (round(v, 1) if isinstance(v, float) else v)
                   for k, v in results.items()}, f, indent=1)
    print(json.dumps(results))
    return results


if __name__ == "__main__":
    main()
