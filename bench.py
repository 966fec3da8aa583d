"""Benchmarks on the local accelerator. Prints ONE JSON line — always.

Default metric mirrors the reference's headline benchmark
(example/image-classification/benchmark_score.py; docs/.../faq/perf.md —
V100 fp16 ResNet-50 batch 128: 2355.04 img/s, BASELINE.md). Select with
argv[1] or BENCH env: resnet (default) | resnet_train | train_step |
train_step_sharded (or ``train_step --shard-update``) |
train_step_fsdp (or ``train_step --shard-params``) |
train_step_multi (or ``train_step --multi-step K``) | lstm_lm |
bert_pretrain | bert_large_pretrain | optimizer_step |
telemetry_overhead | serve | serve_llm | checkpoint.

Robustness contract (round-1 postmortem): any failure — backend init,
compile, OOM — still emits a parseable JSON line with an "error" field and
exits 0, so the driver always records a result. Every mode reports MFU
(achieved model FLOP/s over the chip's peak bf16 FLOP/s).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as onp

BASELINE_RESNET_INFER = 2355.04  # V100 fp16 batch 128 (perf.md:210)
BASELINE_RESNET_TRAIN = 363.69   # V100 fp32 batch 128 training (perf.md:254)
BASELINE_BERT_TOKENS = 10000.0   # A100-class tokens/sec/chip anchor (BASELINE.md)
BASELINE_LSTM_TOKENS = 20000.0   # fused-cuDNN LSTM PTB anchor, tokens/s
# (BASELINE config 3 asks for 'parity with the fused-RNN GPU path'; 20k
# tok/s is the order of a cuDNN 2x650 LSTM at batch 20 on a V100-class
# part — a nominal anchor, the config's bar is qualitative parity)

# analytic model cost per work item (2 FLOPs per MAC)
RESNET50_FWD_FLOPS = 4.089e9          # per image, 224x224
RESNET50_TRAIN_FLOPS = 3 * RESNET50_FWD_FLOPS
BERT_PARAMS = {"base": 110e6, "large": 340e6}

def _device_info():
    # peak bf16 FLOP/s comes from telemetry.costs (one table for bench,
    # step_report MFU and cost_report; MXTPU_PEAK_FLOPS overrides — the
    # only way to get an MFU on a CPU host)
    try:
        import jax

        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", str(dev))
    except Exception:
        return "unknown", None
    try:
        from mxnet_tpu.telemetry.costs import peak_flops_info

        return kind, peak_flops_info()["peak"]
    except Exception:
        return kind, None


def _peak_source():
    try:
        from mxnet_tpu.telemetry.costs import peak_flops_info

        return peak_flops_info()["source"]
    except Exception:
        return None


def _mfu(flops_per_sec):
    _, peak = _device_info()
    if peak is None:
        return None
    return round(flops_per_sec / peak, 4)


def _sync(data):
    # device->host readback: the only reliable barrier on every PJRT backend
    return onp.asarray(data.ravel()[0] if hasattr(data, "ravel") else data)


def _mem_section(top_k=0):
    """Compact memory-ledger slice for a bench JSON (per-program static
    peaks, live-bytes high water, headroom vs the configured limit)."""
    from mxnet_tpu import telemetry

    rep = telemetry.memory_report(top_k)
    return {"program_peak_bytes":
                {site: ent["peak_bytes"]
                 for site, ent in sorted(rep["programs"].items())},
            "live_bytes": rep["live"]["live_bytes"],
            "live_bytes_high_water": rep["live_bytes_high_water"],
            "limit_bytes": rep["limit_bytes"],
            "headroom_fraction": rep["headroom_fraction"]}


def _with_numerics(nmode, fn):
    """Run ``fn`` with MXTPU_NUMERICS pinned (the mode is read at program
    BUILD time, so an on/off comparison needs a fresh compile per leg)."""
    old = os.environ.get("MXTPU_NUMERICS")
    os.environ["MXTPU_NUMERICS"] = nmode
    try:
        return fn()
    finally:
        if old is None:
            os.environ.pop("MXTPU_NUMERICS", None)
        else:
            os.environ["MXTPU_NUMERICS"] = old


def bench_resnet_infer():
    import mxnet_tpu as mx
    from mxnet_tpu.cached_op import trace
    from mxnet_tpu.gluon.model_zoo import vision

    BATCH, WARMUP, ITERS = 128, 3, 10
    net = vision.resnet50_v1()
    net.initialize()
    net.cast("bfloat16")
    x = mx.np.zeros((BATCH, 3, 224, 224), dtype="bfloat16")
    params = [(name, p.data())
              for name, p in net.collect_params().items()
              if p._data is not None]
    _, _, cop = trace(lambda a: net(a), [x], params)
    arrs = [x] + [arr for _, arr in params]
    for _ in range(WARMUP):
        _sync(cop(*arrs)._data)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        out = cop(*arrs)
    _sync(out._data)
    dt = time.perf_counter() - t0
    img_s = BATCH * ITERS / dt
    return {"metric": "resnet50_bf16_infer_batch128",
            "value": round(img_s, 2), "unit": "img/s",
            "vs_baseline": round(img_s / BASELINE_RESNET_INFER, 3),
            "mfu": _mfu(img_s * RESNET50_FWD_FLOPS)}


def bench_resnet_train():
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    from mxnet_tpu import amp

    # "compiled" (default) = Trainer.compile_step, the whole step as ONE
    # donated-buffer program; "learner" = the pre-existing parallel.Learner
    # path (forward+backward program + fused optimizer program)
    path = os.environ.get("BENCH_RESNET_TRAIN_PATH", "compiled")
    BATCH, WARMUP, ITERS = 128, 2, 8
    net = vision.resnet50_v1(classes=1000)
    net.initialize()
    amp.init("bfloat16")  # MXU ops run bf16, params/optimizer state fp32
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = mx.np.random.uniform(size=(BATCH, 3, 224, 224)).astype("bfloat16")
    y = mx.np.random.randint(0, 1000, size=(BATCH,)).astype("float32")
    if path == "compiled":
        trainer = gluon.Trainer(net.collect_params(),
                                mx.optimizer.SGD(learning_rate=0.1,
                                                 momentum=0.9))
        step = trainer.compile_step(net, loss_fn)
        if step.fallback_reason is not None:
            raise RuntimeError("compile_step fell back: "
                               + step.fallback_reason)
    else:
        learner = parallel.Learner(net, loss_fn,
                                   mx.optimizer.SGD(learning_rate=0.1,
                                                    momentum=0.9))
        step = learner.step
    for _ in range(WARMUP):
        _sync(step(x, y)._data)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        loss = step(x, y)
    _sync(loss._data)
    dt = time.perf_counter() - t0
    img_s = BATCH * ITERS / dt
    return {"metric": "resnet50_train_batch128",
            "value": round(img_s, 2), "unit": "img/s",
            "vs_baseline": round(img_s / BASELINE_RESNET_TRAIN, 3),
            "path": path,  # workload variant: keeps rounds comparable
            "mfu": _mfu(img_s * RESNET50_TRAIN_FLOPS)}


def bench_train_step():
    """Whole-step compilation (Trainer.compile_step: ONE donated-buffer
    program per step) against the eager record/backward/``Trainer.step``
    loop, on an MLP+BN classifier. Reports compiled steps/s, the
    compiled/eager ratio, dispatches/step, compile counts (from telemetry,
    measured outside the timed loops), the numerics-monitor overhead
    (steps/s with MXTPU_NUMERICS=cheap vs off) and the static memory
    ledger. BENCH_TRAIN_STEP_SMALL=1 shrinks the model/iterations for the
    not-slow suite."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd as ag, gluon, telemetry
    from mxnet_tpu.gluon import nn

    small = os.environ.get("BENCH_TRAIN_STEP_SMALL", "") == "1"
    B, H, WARMUP, ITERS = (32, 64, 2, 10) if small else (128, 512, 3, 30)

    def make_net():
        mx.random.seed(7)
        net = nn.Sequential()
        net.add(nn.Dense(H, activation="relu"), nn.BatchNorm(),
                nn.Dense(H, activation="relu"), nn.Dense(10))
        net.initialize()
        return net

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = onp.random.RandomState(0)
    x = mx.nd.array(rs.standard_normal((B, H)).astype("float32"))
    y = mx.nd.array(rs.randint(0, 10, (B,)).astype("float32"))
    opt_args = ("sgd", {"learning_rate": 0.05, "momentum": 0.9})

    net_e = make_net()
    tr_e = gluon.Trainer(net_e.collect_params(), *opt_args)

    def eager_step():
        with ag.record():
            loss = loss_fn(net_e(x), y).mean()
        loss.backward()
        tr_e.step(1)
        return loss

    for _ in range(WARMUP):
        _sync(eager_step()._data)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        loss = eager_step()
    _sync(loss._data)
    eager_sps = ITERS / (time.perf_counter() - t0)

    def timed_compiled():
        net_c = make_net()
        tr_c = gluon.Trainer(net_c.collect_params(), *opt_args)
        st = tr_c.compile_step(net_c, loss_fn)
        if st.fallback_reason is not None:
            raise RuntimeError("compile_step fell back: "
                               + st.fallback_reason)
        for _ in range(WARMUP):
            _sync(st(x, y)._data)
        t0 = time.perf_counter()
        for _ in range(ITERS):
            loss = st(x, y)
        _sync(loss._data)
        return st, ITERS / (time.perf_counter() - t0)

    # numerics monitor overhead: same net/loop compiled with the in-program
    # health outputs (cheap, the default) vs without (off)
    step, compiled_sps = _with_numerics("cheap", timed_compiled)
    _, off_sps = _with_numerics("off", timed_compiled)

    # accounting pass AFTER the timed loops: telemetry on, a few steps,
    # read dispatches/recompiles per step from the accountant
    was_on = telemetry.is_enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        for _ in range(3):
            _sync(step(x, y)._data)
        rows = telemetry.step_report()
    finally:
        telemetry.enable() if was_on else telemetry.disable()
    disp = max(r["dispatches"] for r in rows) if rows else -1
    recomp = sum(r["recompiles"] for r in rows) if rows else -1
    flops_step = max((r.get("flops", 0) for r in rows), default=0)
    mfus = [r["mfu"] for r in rows if r.get("mfu") is not None]
    # per-program view: XLA cost_analysis flops joined with the
    # train_step.call timer (telemetry.cost_report)
    prog = telemetry.cost_report().get("train_step") or {}
    return {"metric": "train_step_compiled_mlp",
            "value": round(compiled_sps, 2), "unit": "steps/s",
            "vs_baseline": round(compiled_sps / max(eager_sps, 1e-9), 3),
            "eager_steps_per_sec": round(eager_sps, 2),
            "dispatches_per_step": disp,
            "recompiles_after_warmup": recomp,
            "compiled_programs": step._traces,
            "flops_per_step": int(flops_step),
            "achieved_flops_per_sec":
                (round(prog["achieved_flops_s"], 1)
                 if prog.get("achieved_flops_s") else None),
            "peak_flops_source": _peak_source(),
            "numerics_off_steps_per_sec": round(off_sps, 2),
            "numerics_overhead_pct":
                round(100.0 * (off_sps - compiled_sps) /
                      max(off_sps, 1e-9), 2),
            "memory": _mem_section(),
            "mfu": round(mfus[-1], 4) if mfus else None}


def bench_train_step_sharded():
    """ZeRO-1 sharded weight update (``compile_step(..., shard_update=True)``)
    against the replicated update on the same dp mesh, Adam on an MLP.
    Both settings dispatch the same compiled program (the parity contract),
    so steps/s should match within noise; the win is optimizer-state
    memory. Reports sharded steps/s, the sharded/replicated ratio,
    per-replica vs replicated optimizer-state bytes (from the telemetry
    gauges), and per-step collective bytes. Select with
    ``bench.py train_step --shard-update``. BENCH_TRAIN_STEP_SMALL=1
    shrinks the model/iterations for the not-slow suite."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.mesh import make_mesh

    small = os.environ.get("BENCH_TRAIN_STEP_SMALL", "") == "1"
    B, H, WARMUP, ITERS = (32, 64, 2, 10) if small else (256, 1024, 3, 30)
    mesh = make_mesh()  # every local device on the dp axis
    n_dp = mesh.shape["dp"]
    if n_dp < 2:
        raise RuntimeError(f"sharded update needs dp >= 2, have {n_dp}")

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = onp.random.RandomState(0)
    x = mx.nd.array(rs.standard_normal((B, H)).astype("float32"))
    y = mx.nd.array(rs.randint(0, 10, (B,)).astype("float32"))

    def run(shard):
        mx.random.seed(7)
        net = nn.Sequential()
        net.add(nn.Dense(H, activation="relu"),
                nn.Dense(H, activation="relu"), nn.Dense(10))
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 1e-3})
        step = tr.compile_step(net, loss_fn, mesh=mesh, shard_update=shard)
        if step.fallback_reason is not None:
            raise RuntimeError("compile_step fell back: "
                               + step.fallback_reason)
        for _ in range(WARMUP):
            _sync(step(x, y)._data)
        t0 = time.perf_counter()
        for _ in range(ITERS):
            loss = step(x, y)
        _sync(loss._data)
        return step, ITERS / (time.perf_counter() - t0)

    step_s, sharded_sps = run(True)
    _, replicated_sps = run(False)

    # the state-bytes gauges are sampled once at build time — read them
    # before the accounting reset below wipes them
    per_replica = telemetry.gauge(
        "train_step.opt_state_bytes_per_replica").value
    replicated = telemetry.gauge(
        "train_step.opt_state_bytes_replicated").value

    # accounting pass AFTER the timed loops: telemetry on, a few sharded
    # steps, read per-step dispatch and collective traffic
    was_on = telemetry.is_enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        for _ in range(3):
            _sync(step_s(x, y)._data)
        rows = telemetry.step_report()
    finally:
        telemetry.enable() if was_on else telemetry.disable()
    disp = max(r["dispatches"] for r in rows) if rows else -1
    recomp = sum(r["recompiles"] for r in rows) if rows else -1
    coll = max(r["collective_bytes"] for r in rows) if rows else -1
    return {"metric": "train_step_sharded_update_mlp",
            "value": round(sharded_sps, 2), "unit": "steps/s",
            "vs_baseline": round(sharded_sps / max(replicated_sps, 1e-9), 3),
            "replicated_steps_per_sec": round(replicated_sps, 2),
            "dp_size": n_dp,
            "opt_state_bytes_per_replica": int(per_replica),
            "opt_state_bytes_replicated": int(replicated),
            "collective_bytes_per_step": int(coll),
            "dispatches_per_step": disp,
            "recompiles_after_warmup": recomp,
            "compiled_programs": step_s._traces,
            "mfu": None}


def bench_train_step_fsdp():
    """Full-parameter sharding (``compile_step(..., shard_params=True)``)
    against ZeRO-1 and the fully replicated update on the same dp mesh,
    Adam on an MLP. FSDP moves param + grad + optimizer-state residency to
    1/N per replica at the cost of per-layer just-in-time all_gathers, so
    steps/s trails the replicated program on a host mesh where collectives
    are memcpys and memory is no object — the win column is the residency
    bytes. Reports FSDP steps/s, the FSDP/replicated ratio, ZeRO-1 and
    replicated steps/s, per-replica vs replicated param/grad/state bytes
    (from the telemetry gauges sampled at build), and per-step collective
    bytes. Select with ``bench.py train_step --shard-params``.
    BENCH_TRAIN_STEP_SMALL=1 shrinks the model/iterations for the
    not-slow suite."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.mesh import make_mesh

    small = os.environ.get("BENCH_TRAIN_STEP_SMALL", "") == "1"
    B, H, WARMUP, ITERS = (32, 64, 2, 10) if small else (256, 1024, 3, 30)
    mesh = make_mesh()  # every local device on the dp axis
    n_dp = mesh.shape["dp"]
    if n_dp < 2:
        raise RuntimeError(f"param sharding needs dp >= 2, have {n_dp}")

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = onp.random.RandomState(0)
    x = mx.nd.array(rs.standard_normal((B, H)).astype("float32"))
    y = mx.nd.array(rs.randint(0, 10, (B,)).astype("float32"))

    def run(mode):
        mx.random.seed(7)
        net = nn.Sequential()
        net.add(nn.Dense(H, activation="relu"),
                nn.Dense(H, activation="relu"), nn.Dense(10))
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 1e-3})
        step = tr.compile_step(net, loss_fn, mesh=mesh,
                               shard_params=(mode == "fsdp"),
                               shard_update=(mode == "zero1"))
        if step.fallback_reason is not None:
            raise RuntimeError("compile_step fell back: "
                               + step.fallback_reason)
        for _ in range(WARMUP):
            _sync(step(x, y)._data)
        t0 = time.perf_counter()
        for _ in range(ITERS):
            loss = step(x, y)
        _sync(loss._data)
        sps = ITERS / (time.perf_counter() - t0)
        # the residency gauges are sampled once per build; read them before
        # the next mode's build overwrites them
        g = {k: telemetry.gauge(f"train_step.{k}").value
             for k in ("param_bytes_per_replica", "param_bytes_replicated",
                       "grad_bytes_per_replica",
                       "opt_state_bytes_per_replica",
                       "opt_state_bytes_replicated")}
        return step, sps, g

    _, replicated_sps, _ = run("replicated")
    _, zero1_sps, zero1_g = run("zero1")
    step_f, fsdp_sps, fsdp_g = run("fsdp")
    assert step_f.shard_params is True

    # accounting pass AFTER the timed loops: telemetry on, a few FSDP
    # steps, read per-step dispatch and collective traffic
    was_on = telemetry.is_enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        for _ in range(3):
            _sync(step_f(x, y)._data)
        rows = telemetry.step_report()
    finally:
        telemetry.enable() if was_on else telemetry.disable()
    disp = max(r["dispatches"] for r in rows) if rows else -1
    recomp = sum(r["recompiles"] for r in rows) if rows else -1
    coll = max(r["collective_bytes"] for r in rows) if rows else -1
    return {"metric": "train_step_fsdp_mlp",
            "value": round(fsdp_sps, 2), "unit": "steps/s",
            "vs_baseline": round(fsdp_sps / max(replicated_sps, 1e-9), 3),
            "replicated_steps_per_sec": round(replicated_sps, 2),
            "zero1_steps_per_sec": round(zero1_sps, 2),
            "dp_size": n_dp,
            "param_bytes_per_replica": int(fsdp_g["param_bytes_per_replica"]),
            "param_bytes_replicated": int(fsdp_g["param_bytes_replicated"]),
            "grad_bytes_per_replica": int(fsdp_g["grad_bytes_per_replica"]),
            "opt_state_bytes_per_replica":
                int(fsdp_g["opt_state_bytes_per_replica"]),
            "opt_state_bytes_replicated":
                int(fsdp_g["opt_state_bytes_replicated"]),
            "zero1_opt_state_bytes_per_replica":
                int(zero1_g["opt_state_bytes_per_replica"]),
            "collective_bytes_per_step": int(coll),
            "dispatches_per_step": disp,
            "recompiles_after_warmup": recomp,
            "compiled_programs": step_f._traces,
            "mfu": None}


def bench_train_step_tp():
    """Megatron tensor parallelism composed with FSDP inside the compiled
    step (``compile_step(shard_params=True)`` on a dp x tp mesh with 'tp'
    partition rules): a GPT block trained under the mesh named by
    ``--mesh dpNxtpM`` (BENCH_MESH, default dp4xtp2) against plain FSDP
    with every device on dp. On a host mesh where collectives are memcpys
    the win column is residency — each replica holds 1/(dp*tp) of the
    megatron groups — and the per-axis collective_bytes.dp/.tp split shows
    where the traffic goes. Reports steps/s both ways, the tp/dp-only
    ratio, the per-replica vs replicated param bytes, per-axis collective
    bytes per step, and the dispatch/recompile accounting. Select with
    ``bench.py train_step --mesh dp4xtp2``. BENCH_TRAIN_STEP_SMALL=1
    shrinks the model/iterations for the not-slow suite."""
    import re as _re

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, initializer, telemetry
    from mxnet_tpu.gluon.model_zoo.gpt import gpt_tiny, gpt_tp_rules
    from mxnet_tpu.parallel.mesh import make_mesh

    small = os.environ.get("BENCH_TRAIN_STEP_SMALL", "") == "1"
    spec = os.environ.get("BENCH_MESH", "") or "dp4xtp2"
    m = _re.fullmatch(r"dp(\d+)xtp(\d+)", spec)
    if m is None:
        raise RuntimeError(f"BENCH_MESH must look like dp4xtp2, got {spec!r}")
    n_dp, n_tp = int(m.group(1)), int(m.group(2))
    if small:
        V, B, T, LAYERS, UNITS, WARMUP, ITERS = 67, 8, 12, 2, 64, 2, 8
    else:
        V, B, T, LAYERS, UNITS, WARMUP, ITERS = 384, 16, 32, 4, 128, 3, 20

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.randint(0, V, (B, T)).astype("int32"))
    y = mx.np.array(rs.randint(0, V, (B, T)).astype("int32"))

    def run(mesh_axes, rules):
        mx.random.seed(7)
        net = gpt_tiny(vocab_size=V, dropout=0.0, num_layers=LAYERS,
                       units=UNITS, num_heads=4, max_length=max(T, 16))
        net.initialize(initializer.Normal(0.05))
        net(x)  # settle shapes
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 1e-3})
        step = tr.compile_step(net, loss_fn, mesh=make_mesh(mesh_axes),
                               shard_params=True, partition_rules=rules)
        for _ in range(WARMUP):
            _sync(step(x, y)._data)
        if step.fallback_reason is not None:
            raise RuntimeError("compile_step fell back: "
                               + step.fallback_reason)
        t0 = time.perf_counter()
        for _ in range(ITERS):
            loss = step(x, y)
        _sync(loss._data)
        sps = ITERS / (time.perf_counter() - t0)
        g = {k: telemetry.gauge(f"train_step.{k}").value
             for k in ("param_bytes_per_replica", "param_bytes_replicated")}
        return step, sps, g

    _, dp_sps, _ = run({"dp": n_dp * n_tp}, None)
    step_t, tp_sps, tp_g = run({"dp": n_dp, "tp": n_tp},
                               gpt_tp_rules("train"))
    if not step_t.shard_params:
        raise RuntimeError(step_t.shard_params_fallback_reason)

    # accounting pass AFTER the timed loops: telemetry on, a few dp x tp
    # steps, read the per-step dispatch and per-axis collective traffic
    was_on = telemetry.is_enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        d0 = telemetry.counter("collective_bytes.dp").value
        t0 = telemetry.counter("collective_bytes.tp").value
        for _ in range(3):
            _sync(step_t(x, y)._data)
        rows = telemetry.step_report()
        dp_bytes = (telemetry.counter("collective_bytes.dp").value - d0) // 3
        tp_bytes = (telemetry.counter("collective_bytes.tp").value - t0) // 3
    finally:
        telemetry.enable() if was_on else telemetry.disable()
    disp = max(r["dispatches"] for r in rows) if rows else -1
    recomp = sum(r["recompiles"] for r in rows) if rows else -1
    return {"metric": "train_step_tp_gpt",
            "value": round(tp_sps, 2), "unit": "steps/s",
            "vs_baseline": round(tp_sps / max(dp_sps, 1e-9), 3),
            "dp_only_steps_per_sec": round(dp_sps, 2),
            "mesh": spec, "dp_size": n_dp, "tp_size": n_tp,
            "param_bytes_per_replica": int(tp_g["param_bytes_per_replica"]),
            "param_bytes_replicated": int(tp_g["param_bytes_replicated"]),
            "collective_bytes_dp_per_step": int(dp_bytes),
            "collective_bytes_tp_per_step": int(tp_bytes),
            "dispatches_per_step": disp,
            "recompiles_after_warmup": recomp,
            "compiled_programs": step_t._traces,
            "mfu": None}


def bench_train_step_multi():
    """Scanned super-step execution (``compile_step(multi_step=K)``): K
    optimizer steps per dispatch via ``lax.scan``, fed by a
    ``DevicePrefetcher`` that stacks + stages the next super-batch while
    the current one computes. Sweeps K over {1, 4, 16} on the dp mesh and
    reports steps/s, HOST ms per step (dispatch-side cost, the quantity
    the scan amortizes — device compute per step is constant on a host
    mesh) and dispatches/step (1/K). K=1 runs through the same scanned
    machinery, so the sweep isolates the super-step amortization. Select
    with ``bench.py train_step --multi-step K`` (K = the headline row;
    every swept K lands in ``sweep``). BENCH_TRAIN_STEP_SMALL=1 shrinks
    the model/iterations for the not-slow suite."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.data import DevicePrefetcher
    from mxnet_tpu.parallel.mesh import make_mesh

    small = os.environ.get("BENCH_TRAIN_STEP_SMALL", "") == "1"
    B, H, WARMUP, ITERS = (32, 64, 1, 4) if small else (64, 256, 2, 12)
    ks = [1, 4] if small else [1, 4, 16]
    want_k = int(os.environ.get("BENCH_MULTI_STEP", "0")) or ks[-1]
    if want_k not in ks:
        ks.append(want_k)
    mesh = make_mesh()
    n_dp = mesh.shape["dp"]

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = onp.random.RandomState(0)
    x_np = rs.standard_normal((B, H)).astype("float32")
    y_np = rs.randint(0, 10, (B,)).astype("float32")

    def run_k(k):
        mx.random.seed(7)
        net = nn.Sequential()
        net.add(nn.Dense(H, activation="relu"), nn.BatchNorm(),
                nn.Dense(H, activation="relu"), nn.Dense(10))
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        step = tr.compile_step(net, loss_fn, mesh=mesh, multi_step=k)
        batches = [(x_np, y_np)] * (k * (WARMUP + ITERS))
        pf = DevicePrefetcher(batches, multi_step=k)
        it = iter(pf)
        # telemetry stays ON for the whole sweep leg: the host-ms gauge
        # and super-step rows are the measurement (same overhead at
        # every K, so the ratios are clean)
        telemetry.reset()
        for _ in range(WARMUP):
            _sync(step(*next(it))._data)
        c0 = telemetry.compile_count()
        host_ms = []
        t0 = time.perf_counter()
        for _ in range(ITERS):
            loss = step(*next(it))
            host_ms.append(telemetry.gauge("train.host_ms_per_step").value)
        _sync(loss._data)
        dt = time.perf_counter() - t0
        pf.close()
        row = telemetry.last_step() or {}
        return {"steps_per_sec": round(k * ITERS / dt, 2),
                "host_ms_per_step": round(sum(host_ms) / len(host_ms), 4),
                "dispatches_per_step":
                    round(row.get("dispatches_per_step", -1), 4),
                "recompiles_after_warmup":
                    telemetry.compile_count() - c0,
                "compiled_programs": step._traces}

    was_on = telemetry.is_enabled()
    telemetry.enable()
    try:
        # the sweep runs with the in-program numerics monitor on (cheap,
        # the default); one extra off leg at the headline K measures its
        # steps/s overhead — same dispatches/step both ways by design
        sweep = {str(k): _with_numerics("cheap", lambda k=k: run_k(k))
                 for k in ks}
        off = _with_numerics("off", lambda: run_k(want_k))
    finally:
        telemetry.enable() if was_on else telemetry.disable()
    head = sweep[str(want_k)]
    base = sweep[str(ks[0])]
    return {"metric": f"train_step_multi_step_k{want_k}",
            "value": head["steps_per_sec"], "unit": "steps/s",
            "vs_baseline": round(head["steps_per_sec"] /
                                 max(base["steps_per_sec"], 1e-9), 3),
            "host_ms_per_step": head["host_ms_per_step"],
            "host_ms_speedup_vs_k1":
                round(base["host_ms_per_step"] /
                      max(head["host_ms_per_step"], 1e-9), 2),
            "dispatches_per_step": head["dispatches_per_step"],
            "recompiles_after_warmup": head["recompiles_after_warmup"],
            "dp_size": int(n_dp),
            "numerics_off_steps_per_sec": off["steps_per_sec"],
            "numerics_overhead_pct":
                round(100.0 * (off["steps_per_sec"] - head["steps_per_sec"])
                      / max(off["steps_per_sec"], 1e-9), 2),
            "sweep": sweep,
            "memory": _mem_section(),
            "mfu": None}


def bench_lstm_lm():
    """LSTM language model training step over the fused lax.scan RNN
    (BASELINE config 3: 'LSTM PTB LM — parity with fused-RNN GPU path').
    PTB-shaped: vocab 10k, 2x650 LSTM, batch 20, bptt 35."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon, parallel
    from mxnet_tpu.gluon.model_zoo.rnn_lm import rnn_lm

    B, T, WARMUP, ITERS = 20, 35, 2, 8
    net = rnn_lm(vocab_size=10000, embed_size=650, hidden_size=650,
                 num_layers=2, dropout=0.5)
    net.initialize()
    amp.init("bfloat16")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def lm_loss(logits, labels):
        return loss_fn(logits.reshape(-1, 10000),
                       labels.reshape(-1)).mean()

    learner = parallel.Learner(net, lm_loss,
                               mx.optimizer.SGD(learning_rate=1.0))
    x = mx.np.random.randint(0, 10000, size=(B, T))
    y = mx.np.random.randint(0, 10000, size=(B, T)).astype("float32")
    for _ in range(WARMUP):
        _sync(learner.step(x, y)._data)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        loss = learner.step(x, y)
    _sync(loss._data)
    dt = time.perf_counter() - t0
    tok_s = B * T * ITERS / dt
    return {"metric": "lstm_lm_ptb_train", "value": round(tok_s, 1),
            "unit": "tokens/s",
            "vs_baseline": round(tok_s / BASELINE_LSTM_TOKENS, 3),
            "mfu": None}


def bench_bert_pretrain(size="base"):
    """BERT MLM+NSP pretraining step, bf16, one chip (configs 4 and the
    BERT-Large north-star metric)."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon, parallel
    from mxnet_tpu.gluon.model_zoo.bert import bert_base, BERTForPretraining

    from mxnet_tpu.gluon.model_zoo.bert import bert_large

    B = 32 if size == "base" else 8
    T, WARMUP, ITERS = 128, 2, 8
    maker = bert_base if size == "base" else bert_large
    bert = maker(max_length=T, dropout=0.1, dtype="float32")
    model = BERTForPretraining(bert, vocab_size=30522)

    padded = os.environ.get("BENCH_BERT_PADDED", "1") == "1"
    if padded:
        # realistic padded batches: a fixed 7/8-valid key-padding mask per
        # row keeps attention on the fused segment-ids flash path (the
        # HLO carries the masked kernel, not an O(T²) where-mask)
        class _PaddedBERT(gluon.HybridBlock):
            def __init__(self, inner, t_valid):
                super().__init__()
                self.inner = inner
                self._t_valid = t_valid

            def forward(self, tokens):
                vlen = mx.np.full((tokens.shape[0],), self._t_valid,
                                  dtype="float32")
                return self.inner(tokens, None, vlen)

        model = _PaddedBERT(model, T * 7 // 8)
    model.initialize()
    amp.convert_hybrid_block(model, "bfloat16")
    amp.init("bfloat16")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def pretrain_loss(pair, labels):
        mlm_scores, nsp_scores = pair
        mlm_labels, nsp_labels = labels[:, :-1], labels[:, -1]
        return loss_fn(mlm_scores, mlm_labels).mean() + \
            loss_fn(nsp_scores, nsp_labels).mean()

    learner = parallel.Learner(model, pretrain_loss,
                               mx.optimizer.AdamW(learning_rate=1e-4,
                                                  wd=0.01),
                               remat=(size == "large"))
    tokens = mx.np.random.randint(0, 30522, size=(B, T))
    labels = mx.np.concatenate([
        mx.np.random.randint(0, 30522, size=(B, T)),
        mx.np.random.randint(0, 2, size=(B, 1))], axis=1).astype("float32")
    for _ in range(WARMUP):
        _sync(learner.step(tokens, labels)._data)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        loss = learner.step(tokens, labels)
    _sync(loss._data)
    dt = time.perf_counter() - t0
    tok_s = B * T * ITERS / dt
    return {"metric": f"bert_{size}_pretrain_bf16_tokens_per_sec",
            "value": round(tok_s, 1), "unit": "tokens/s",
            "vs_baseline": round(tok_s / BASELINE_BERT_TOKENS, 3),
            "padded": padded,  # workload variant: keeps rounds comparable
            "mfu": _mfu(tok_s * 6 * BERT_PARAMS[size])}


def _resnet50_param_shapes():
    """ResNet-50-shaped tensor set: stem conv + BN pair, 16 bottleneck
    blocks (3 conv kernels + 3 BN gamma/beta pairs each), a downsample
    conv + BN pair per stage, and the fc head — 163 tensors, ~25M params."""
    shapes = [(64, 3, 7, 7), (64,), (64,)]
    for blocks, cin, cmid in [(3, 256, 64), (4, 512, 128),
                              (6, 1024, 256), (3, 2048, 512)]:
        shapes += [(cin, cin // 2 if cin > 256 else 64, 1, 1), (cin,),
                   (cin,)]  # stage downsample projection
        for _ in range(blocks):
            shapes += [(cmid, cin, 1, 1), (cmid,), (cmid,),
                       (cmid, cmid, 3, 3), (cmid,), (cmid,),
                       (cin, cmid, 1, 1), (cin,), (cin,)]
    shapes += [(1000, 2048), (1000,)]
    return shapes


def _build_param_set(shapes, seed=0):
    import jax.numpy as jnp

    from mxnet_tpu.gluon.parameter import Parameter

    rng = onp.random.RandomState(seed)
    params = []
    for j, shp in enumerate(shapes):
        p = Parameter(name=f"p{j}", shape=shp)
        p.initialize()
        p.set_data(jnp.asarray(rng.standard_normal(shp), jnp.float32))
        p.grad()._set_data(
            jnp.asarray(rng.standard_normal(shp), jnp.float32))
        params.append(p)
    return params


def bench_optimizer_step():
    """Fused vs per-param optimizer step over a ResNet-50-sized synthetic
    parameter set (~160 tensors, ~25M params): Trainer.update with the
    fused multi-tensor path on vs off. Reports updates/sec both ways and
    per-step compiled-call counts (fused: O(#buckets); per-param:
    O(#params))."""
    from mxnet_tpu import gluon, optimizer

    shapes = _resnet50_param_shapes()

    def build():
        return _build_param_set(shapes)

    WARMUP, ITERS = 3, 10

    def run(fuse):
        import jax

        params = build()
        tr = gluon.Trainer(params, optimizer.SGD(learning_rate=0.01,
                                                 momentum=0.9))
        tr._fuse = fuse
        for _ in range(WARMUP):
            tr.update(32)
        jax.block_until_ready([p.data()._data for p in params])
        d0 = tr._fused_dispatches
        t0 = time.perf_counter()
        for _ in range(ITERS):
            tr.update(32)
        jax.block_until_ready([p.data()._data for p in params])
        dt = time.perf_counter() - t0
        dispatch = (tr._fused_dispatches - d0) // ITERS if fuse \
            else len(params)
        return len(params) * ITERS / dt, dispatch

    fused_ups, fused_disp = run(True)
    pp_ups, pp_disp = run(False)
    return {"metric": "optimizer_step_fused_resnet50_161tensors",
            "value": round(fused_ups, 1), "unit": "updates/s",
            "vs_baseline": round(fused_ups / max(pp_ups, 1e-9), 3),
            "per_param_updates_per_sec": round(pp_ups, 1),
            "dispatches_fused": fused_disp,
            "dispatches_per_param": pp_disp,
            "mfu": None}


def bench_telemetry_overhead():
    """Enabled-telemetry overhead on the fused optimizer_step bench.

    One trainer, jit caches warmed once, then interleaved off/on timing
    trials; the reported overhead is the ratio of the min-of-trials each
    way — robust to one-off scheduler noise. A second surface covers the
    serve submit path with per-request tracing live (exporter off): the
    RequestTrace allocation + phase marks ride the same interleaved
    pairwise-min protocol. BENCH_TELEM_SMALL=1 shrinks the tensor set
    (for the not-slow test); the acceptance bar is < 2%.
    """
    import jax

    from mxnet_tpu import gluon, optimizer, telemetry

    shapes = _resnet50_param_shapes()
    small = os.environ.get("BENCH_TELEM_SMALL", "") == "1"
    if small:
        shapes = shapes[:40]
    params = _build_param_set(shapes)
    tr = gluon.Trainer(params, optimizer.SGD(learning_rate=0.01,
                                             momentum=0.9))

    # the small set's per-iter time is tiny, so buy noise robustness with
    # more, longer trials — still ~2s of measurement
    WARMUP, ITERS, TRIALS = (3, 25, 8) if small else (3, 10, 5)

    was_on = telemetry.is_enabled()
    try:
        # warm the jit caches under BOTH modes so neither timed loop pays
        # a trace (the observer is baked in at trace time either way; only
        # the runtime ON checks differ between modes)
        for enabled in (False, True):
            telemetry.enable() if enabled else telemetry.disable()
            for _ in range(WARMUP):
                tr.update(32)
        jax.block_until_ready([p.data()._data for p in params])

        def timed(enabled):
            telemetry.enable() if enabled else telemetry.disable()
            t0 = time.perf_counter()
            for _ in range(ITERS):
                tr.update(32)
            jax.block_until_ready([p.data()._data for p in params])
            return time.perf_counter() - t0

        t_off, t_on = [], []
        for _ in range(TRIALS):
            t_off.append(timed(False))
            t_on.append(timed(True))
    finally:
        telemetry.enable() if was_on else telemetry.disable()

    # tracing surface: batched submits through a warmed Predictor with
    # max_wait_us=0 — telemetry on allocates a RequestTrace + 4 phase
    # marks per request; off is a single bool check (new_trace -> None)
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn

    mx.random.seed(5)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
    net.initialize()
    net.hybridize()
    pred = net.predictor(example=mx.nd.array(
        onp.zeros((8, 16), "float32")), max_batch=8, max_wait_us=0)
    S_WARM, S_ITERS, S_TRIALS = 10, (40 if small else 80), 6
    item = onp.zeros(16, "float32")

    def timed_serve(enabled):
        telemetry.enable() if enabled else telemetry.disable()
        t0 = time.perf_counter()
        for _ in range(S_ITERS):
            # 8 in-flight futures per wave: the trace cost is per request,
            # the dispatch handoff cost amortizes over the wave
            for f in [pred.submit(item) for _ in range(8)]:
                f.result(60)
        return time.perf_counter() - t0

    try:
        pred.warmup()
        for enabled in (False, True):
            telemetry.enable() if enabled else telemetry.disable()
            for _ in range(S_WARM):
                pred.submit(item).result(60)
        s_off, s_on = [], []
        for _ in range(S_TRIALS):
            s_off.append(timed_serve(False))
            s_on.append(timed_serve(True))
    finally:
        pred.close()
        telemetry.enable() if was_on else telemetry.disable()

    # each off/on pair runs back-to-back, so ambient load is comparable
    # within a pair; the min over pair ratios filters box noise that a
    # min-of-each-side comparison cannot (no trial window may be quiet)
    overhead = min(on / max(off, 1e-12)
                   for off, on in zip(t_off, t_on)) - 1.0
    pct = overhead * 100.0
    serve_pct = (min(on / max(off, 1e-12)
                     for off, on in zip(s_off, s_on)) - 1.0) * 100.0
    return {"metric": "telemetry_overhead_optimizer_step",
            "value": round(pct, 3), "unit": "%",
            "vs_baseline": round(pct / 2.0, 3),  # fraction of the 2% budget
            "threshold_pct": 2.0,
            "n_tensors": len(shapes),
            "updates_per_sec_off": round(len(shapes) * ITERS / min(t_off), 1),
            "updates_per_sec_on": round(len(shapes) * ITERS / min(t_on), 1),
            "serve_tracing_overhead_pct": round(serve_pct, 3),
            "serve_req_per_sec_off":
                round(8 * S_ITERS * 1.0 / min(s_off), 1),
            "serve_req_per_sec_on":
                round(8 * S_ITERS * 1.0 / min(s_on), 1),
            "mfu": None}


def bench_serve():
    """Inference fast path (serve.Predictor): 64 concurrent single-item
    clients through the shape-bucketed dynamic batcher vs the same thread
    harness doing naive per-request eager forwards on a non-hybridized
    copy of the net. Reports req/s both ways, the serve/eager ratio
    (acceptance bar: >= 3x), batch/dispatch accounting, padding waste,
    p50/p99 latency, and compile counts — steady-state compiles after
    warmup() must be 0. BENCH_SERVE_SMALL=1 shrinks clients/model for
    the not-slow suite."""
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon import nn

    small = os.environ.get("BENCH_SERVE_SMALL", "") == "1"
    CLIENTS, REQS, FEAT, HID = (16, 4, 32, 64) if small else (64, 8, 128, 256)

    def make_net(hybrid):
        mx.random.seed(11)
        net = nn.HybridSequential()
        net.add(nn.Dense(HID, activation="relu"),
                nn.Dense(HID, activation="relu"), nn.Dense(10))
        net.initialize()
        if hybrid:
            net.hybridize()
        return net

    rs = onp.random.RandomState(3)
    items = rs.standard_normal((CLIENTS * REQS, FEAT)).astype("float32")

    def drive(worker):
        # identical harness both ways: CLIENTS threads, REQS requests
        # each, all released together; throughput over the joined wall
        barrier = threading.Barrier(CLIENTS + 1)
        errs = []

        def client(cid):
            try:
                barrier.wait()
                for r in range(REQS):
                    worker(items[cid * REQS + r])
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(CLIENTS)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errs:
            raise errs[0]
        return CLIENTS * REQS / dt

    was_on = telemetry.is_enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        # baseline: per-request eager forward, one item per call
        net_e = make_net(hybrid=False)

        def eager_worker(item):
            _sync(net_e(mx.nd.array(item[None, :]))._data)

        for k in range(3):  # warm the per-op programs
            eager_worker(items[k])
        eager_rps = drive(eager_worker)

        # fast path: warmed Predictor, futures-based dynamic batching
        pred = make_net(hybrid=True).predictor(
            example=mx.nd.array(items[:CLIENTS]), max_batch=CLIENTS)
        pred.warmup()
        compiles_warmup = int(telemetry.metrics()["jit.compiles"])
        yref = net_e(mx.nd.array(items[:1])).asnumpy()
        ygot = pred.predict(mx.nd.array(items[:1])).asnumpy()
        onp.testing.assert_allclose(ygot, yref, rtol=2e-4, atol=2e-4)

        c0 = telemetry.metrics()["jit.compiles"]
        serve_rps = drive(lambda item: pred.submit(item).result(120))
        compiles_steady = int(telemetry.metrics()["jit.compiles"] - c0)
        st = pred.stats()
        pred.close()
    finally:
        telemetry.enable() if was_on else telemetry.disable()

    return {"metric": "serve_dynamic_batch_64clients",
            "value": round(serve_rps, 1), "unit": "req/s",
            "vs_baseline": round(serve_rps / max(eager_rps, 1e-9), 3),
            "eager_req_per_sec": round(eager_rps, 1),
            "clients": CLIENTS, "requests": CLIENTS * REQS,
            "dispatches": st["batches"],
            "mean_occupancy": st["mean_occupancy"],
            "padding_waste": st["padding_waste"],
            "latency_ms_p50": st["latency_ms_p50"],
            "latency_ms_p99": st["latency_ms_p99"],
            "compiles_warmup": compiles_warmup,
            "compiles_steady": compiles_steady,
            "mfu": None}


def bench_serve_llm():
    """Continuous-batching decode (serve.decode.DecodeEngine): 64
    concurrent clients with ragged prompt lengths streaming greedy tokens
    from gpt_tiny, vs the same thread harness running the naive
    per-request ``generate(use_cache=False)`` rolling-window loop.
    Reports generated tokens/s both ways, the engine/naive ratio, p50/p99
    TTFT and per-token latency from the telemetry Histograms, slot
    occupancy, and compile counts — steady-state compiles after warmup()
    must be 0. BENCH_SERVE_LLM_SMALL=1 shrinks clients/model for the
    not-slow suite.

    Decode-v2 variants (CLI flags on ``bench.py serve_llm`` / env):
    ``--speculate K`` (BENCH_SPECULATE_K) verifies K tokens per tick;
    ``--prefix-shared PCT`` (BENCH_PREFIX_SHARED) gives PCT%% of clients
    a shared multi-page prompt prefix so the radix cache skips its
    re-prefill; ``--paged`` (BENCH_PAGED=1) doubles num_slots while
    pinning the page pool to the UN-doubled reservation — 2x concurrency
    at equal KV bytes; ``--tp N`` (BENCH_SERVE_TP) serves the model
    tensor-parallel over a {'tp': N} mesh — column-sharded weights,
    head-sharded KV pools, greedy output still bitwise vs naive."""
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon.model_zoo import gpt_tiny
    from mxnet_tpu.serve.decode import DecodeEngine

    small = os.environ.get("BENCH_SERVE_LLM_SMALL", "") == "1"
    if small:
        CLIENTS, MAX_NEW, SLOTS, UNITS, LAYERS, MAX_LEN, MAX_PROMPT = \
            (8, 4, 4, 32, 2, 64, 12)
    else:
        CLIENTS, MAX_NEW, SLOTS, UNITS, LAYERS, MAX_LEN, MAX_PROMPT = \
            (64, 16, 16, 64, 2, 128, 48)
    # generation length knob: the default workload is prefill-heavy
    # (prompts ~ MAX_PROMPT, few new tokens); raising MAX_NEW makes the
    # measurement decode-dominated, where per-tick levers (speculation)
    # show up in wall clock instead of being Amdahl-capped by prefill
    MAX_NEW = int(os.environ.get("BENCH_MAX_NEW", "") or MAX_NEW)
    MAX_NEW = min(MAX_NEW, MAX_LEN - MAX_PROMPT)
    VOCAB = 256
    tp = int(os.environ.get("BENCH_SERVE_TP", "1") or 1)
    speculate = int(os.environ.get("BENCH_SPECULATE_K", "0") or 0)
    prefix_pct = max(0, min(100, int(
        os.environ.get("BENCH_PREFIX_SHARED", "0") or 0)))
    paged2x = os.environ.get("BENCH_PAGED", "") == "1"
    v2 = bool(speculate or prefix_pct or paged2x)
    PAGE = 8 if small else 16  # v2 variants only; default clamps to max_len

    mx.random.seed(23)
    net = gpt_tiny(vocab_size=VOCAB, dropout=0.0, num_layers=LAYERS,
                   units=UNITS, num_heads=4, max_length=MAX_LEN)
    net.initialize()
    rs = onp.random.RandomState(7)
    prompts = [[int(t) for t in rs.randint(1, VOCAB,
                                           size=rs.randint(1, MAX_PROMPT))]
               for _ in range(CLIENTS)]
    if prefix_pct:
        # a shared "system prompt" covering >= 1 full page, so the radix
        # cache can map it read-only into every sharer's page table
        span = max(PAGE, (MAX_PROMPT - 4) // PAGE * PAGE)
        shared = [int(t) for t in rs.randint(1, VOCAB, size=span)]
        for i in range(CLIENTS * prefix_pct // 100):
            tail = 1 + rs.randint(max(1, MAX_PROMPT - span))
            prompts[i] = shared + prompts[i][:tail]

    def drive(worker):
        # identical harness both ways: one thread per client, all released
        # together; tokens/s over the joined wall clock
        barrier = threading.Barrier(CLIENTS + 1)
        errs, tokens = [], [0] * CLIENTS

        def client(cid):
            try:
                barrier.wait()
                tokens[cid] = len(worker(prompts[cid]))
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(CLIENTS)]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errs:
            raise errs[0]
        return sum(tokens) / dt, sum(tokens)

    was_on = telemetry.is_enabled()
    telemetry.reset()
    telemetry.enable()
    try:
        # baseline: the naive rolling-window loop, one forward per token
        def naive_worker(prompt):
            out = net.generate(prompt, max_new_tokens=MAX_NEW,
                               temperature=0.0, use_cache=False)
            return out[len(prompt):]

        naive_worker(prompts[0])  # warm the window program
        naive_tps, _ = drive(naive_worker)

        slots = SLOTS * 2 if paged2x else SLOTS
        kw = dict(num_slots=slots, max_len=MAX_LEN,
                  max_prompt_len=MAX_PROMPT, prefill_batch=min(slots, 4),
                  max_queue=2 * CLIENTS, cache_dir=False)
        if v2:
            kw.update(page_tokens=PAGE, speculate_k=max(1, speculate),
                      prefix_cache=True)
        if tp > 1:
            kw["tp"] = tp
        if paged2x:
            # equal-bytes contract: the pool stays at the UN-doubled
            # slot reservation while num_slots doubles
            kw["kv_pages"] = SLOTS * (-(-MAX_LEN // PAGE))
        eng = DecodeEngine(net, **kw)
        eng.warmup()
        compiles_warmup = int(telemetry.metrics()["jit.compiles"])
        # greedy parity spot check before timing anything
        want = naive_worker(prompts[0])
        got = eng.submit(prompts[0], max_new_tokens=MAX_NEW).result(120)
        if got != [int(t) for t in want]:
            raise AssertionError(
                f"engine/naive greedy divergence: {got} vs {want}")

        c0 = telemetry.metrics()["jit.compiles"]
        f0 = telemetry.metrics().get("telemetry.flops", 0.0)
        t_drive = time.perf_counter()
        engine_tps, n_tokens = drive(
            lambda p: eng.submit(p, max_new_tokens=MAX_NEW).result(300))
        wall = time.perf_counter() - t_drive
        f1 = telemetry.metrics().get("telemetry.flops", 0.0)
        compiles_steady = int(telemetry.metrics()["jit.compiles"] - c0)
        # per-request phase decomposition (queue -> prefill -> decode) of
        # the traces the engine finished during the drive
        lat = (telemetry.latency_report("serve.decode")
               or {}).get("serve.decode") or {}
        tps_chip = telemetry.gauge("serve.tokens_per_s_chip").value
        st = eng.stats()
        mem = _mem_section()  # while the engine (KV cache, slots) is live
        eng.close()
    finally:
        telemetry.enable() if was_on else telemetry.disable()

    achieved = (f1 - f0) / max(wall, 1e-9)
    return {"metric": "serve_llm_continuous_batching",
            "value": round(engine_tps, 1), "unit": "tok/s",
            "vs_baseline": round(engine_tps / max(naive_tps, 1e-9), 3),
            "naive_tok_per_sec": round(naive_tps, 1),
            "clients": CLIENTS, "tokens": n_tokens,
            "ticks": st["ticks"], "prefills": st["prefills"],
            "mean_slot_occupancy": round(st["mean_slot_occupancy"], 3),
            "ttft_ms_p50": st["ttft_ms_p50"],
            "ttft_ms_p99": st["ttft_ms_p99"],
            "tpot_ms_p50": st["tpot_ms_p50"],
            "tpot_ms_p99": st["tpot_ms_p99"],
            "latency_ms_p99": (lat.get("total_ms") or {}).get("p99"),
            "latency_p99_decomposition_ms": lat.get("p99_attribution_ms"),
            "tokens_per_s_chip": round(tps_chip, 1) if tps_chip else None,
            "shed": st["shed"], "evicted": st["evicted"],
            "compiles_warmup": compiles_warmup,
            "compiles_steady": compiles_steady,
            "speculate_k": st["speculate_k"],
            "spec_accept_mean": (round(st["spec_accept_mean"], 3)
                                 if "spec_accept_mean" in st else None),
            "tp": tp,
            "prefix_shared_pct": prefix_pct,
            "prefix_hit_tokens": st["prefix_hit_tokens"],
            "prompt_tokens": sum(len(p) for p in prompts),
            "page_tokens": st["page_tokens"],
            "kv_pages": st["kv_pages"],
            "num_slots": st["num_slots"],
            "paged_2x_slots": paged2x,
            "page_starved": st["page_starved"],
            "kv_cache_bytes": st["cache_bytes"],
            "achieved_flops_per_sec": round(achieved, 1),
            "peak_flops_source": _peak_source(),
            "memory": mem,
            "mfu": _mfu(achieved)}


def bench_checkpoint():
    """Checkpoint save stall: p99 step time of a compiled train loop with
    NO saves vs SYNC saves vs ASYNC saves (every EVERY steps), plus the
    `checkpoint.save_stall_ms` histogram per regime. Headline is the
    async p99 step-time inflation over the no-checkpoint baseline in
    percent (the acceptance bar is <10%); `vs_baseline` carries the
    sync-vs-async p99 stall ratio (how much stall the background writer
    removes from the step boundary). BENCH_CHECKPOINT_SMALL=1 shrinks
    the model/iterations for the not-slow suite."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.checkpoint import CheckpointManager
    from mxnet_tpu.gluon import nn

    small = os.environ.get("BENCH_CHECKPOINT_SMALL", "") == "1"
    B, H, WARMUP, ITERS, EVERY = (16, 32, 2, 12, 2) if small \
        else (64, 256, 5, 100, 5)

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rs = onp.random.RandomState(0)
    x = mx.nd.array(rs.standard_normal((B, H)).astype("float32"))
    y = mx.nd.array(rs.randint(0, 10, (B,)).astype("float32"))

    def make():
        mx.random.seed(7)
        net = nn.Sequential()
        net.add(nn.Dense(H, activation="relu"), nn.Dense(H),
                nn.Dense(10))
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05})
        step = tr.compile_step(net, loss_fn)
        return net, tr, step

    def run(mode):
        telemetry.reset()  # per-regime checkpoint.* metrics
        net, tr, step = make()
        mgr, tmpd, times = None, None, []
        try:
            if mode != "none":
                tmpd = tempfile.mkdtemp(prefix="mxtpu_bench_ckpt_")
                mgr = CheckpointManager(tmpd, trainer=tr, net=net, keep=2,
                                        async_save=(mode == "async"))
            for _ in range(WARMUP):
                _sync(step(x, y)._data)
            for i in range(1, ITERS + 1):
                t0 = time.perf_counter()
                _sync(step(x, y)._data)
                if mgr is not None and i % EVERY == 0:
                    mgr.save(i)
                times.append(time.perf_counter() - t0)
            if mgr is not None:
                mgr.wait()
        finally:
            if mgr is not None:
                mgr.close()
            if tmpd:
                shutil.rmtree(tmpd, ignore_errors=True)
        arr = onp.asarray(times) * 1e3
        stall = telemetry.REGISTRY.histogram("checkpoint.save_stall_ms")
        s50, s99 = stall.percentiles(50, 99)
        return {"p50_ms": round(float(onp.percentile(arr, 50)), 3),
                "p99_ms": round(float(onp.percentile(arr, 99)), 3),
                "mean_ms": round(float(arr.mean()), 3),
                "stall_ms_p50": round(s50, 3) if s50 is not None else None,
                "stall_ms_p99": round(s99, 3) if s99 is not None else None}

    base, sync, async_ = run("none"), run("sync"), run("async")
    p99_delta_pct = 100.0 * (async_["p99_ms"] - base["p99_ms"]) \
        / max(base["p99_ms"], 1e-9)
    stall_ratio = (sync["stall_ms_p99"] or 0.0) \
        / max(async_["stall_ms_p99"] or 0.0, 1e-9)
    return {"metric": "checkpoint_async_p99_step_inflation",
            "value": round(p99_delta_pct, 2), "unit": "%",
            "vs_baseline": round(stall_ratio, 3),
            "steps": ITERS, "save_every": EVERY,
            "no_ckpt": base, "sync_save": sync, "async_save": async_,
            "async_under_10pct": bool(p99_delta_pct < 10.0),
            "mfu": None}


def _kernel_bench_specs(small):
    """The tuned-vs-default measurement matrix: three kernel families
    across the serving bucket ladder's shape classes."""
    from mxnet_tpu import tune

    if small:
        return [
            tune.attention_spec("flash_fwd", 1, 2, 64, 64, 32,
                                causal=True),
            tune.rows_spec("layer_norm", 128, 128),
            tune.rows_spec("softmax", 128, 128),
        ]
    specs = []
    # attention over three (batch*heads, seq) ladder rungs — GPT decode
    # prefill shapes (causal) at head_dim 64
    for b, t in ((1, 128), (2, 256), (4, 512)):
        specs.append(tune.attention_spec("flash_fwd", b, 4, t, t, 64,
                                         causal=True))
    # row-wise kernels over three row-bucket rungs at d_model 256
    for rows in (128, 512, 2048):
        specs.append(tune.rows_spec("layer_norm", rows, 256))
        specs.append(tune.rows_spec("softmax", rows, 256))
    return specs


def bench_kernels():
    """Tuned-vs-default kernel latency across the bucket ladder.

    Runs the autotuner's own measurement harness (compile-once then
    interleaved pairwise-min trials) per (kernel, bucket) spec and
    reports each spec's default-config time, winner, and speedup. On the
    CPU mesh Pallas runs in interpret mode, where the XLA lowering
    usually wins — exactly the "never silently slower" contract the
    resolve tier enforces; the tuned win reported here is real measured
    time but validates the MECHANISM, not TPU block tuning (see the
    tpu_note field). BENCH_KERNELS_SMALL=1 shrinks the matrix for the
    not-slow smoke.
    """
    from mxnet_tpu import telemetry, tune
    from mxnet_tpu.context import default_backend

    on_cpu = default_backend() == "cpu"
    if on_cpu:
        # exercise the Pallas kernel paths (interpret mode) so candidates
        # differ; without this every config lowers to the same XLA ref
        os.environ.setdefault("MXTPU_PALLAS_INTERPRET", "1")
    small = os.environ.get("BENCH_KERNELS_SMALL", "") == "1"
    specs = _kernel_bench_specs(small)
    tune.reset()
    _use_bench_tune_cache()
    wd_before = dict(telemetry.watchdog_stats())
    results = tune.autotune(specs, trials=(2 if small else 4),
                            max_per_axis=(2 if small else 3), save=True)
    rows = []
    for r in results:
        rows.append({"key": r["key"], "winner": r["winner"],
                     "config": r["config"],
                     "default_us": round(r["default_us"], 1),
                     "best_us": round(r["best_us"], 1),
                     "speedup_vs_default":
                         round(r["speedup_vs_default"], 3)})
    kernels_with_win = sorted({r["kernel"] for r in results
                               if r["speedup_vs_default"] > 1.0})
    speedups = [r["speedup_vs_default"] for r in results]
    geo = float(onp.exp(onp.mean(onp.log(onp.maximum(speedups, 1e-9)))))
    return {"metric": "kernel_tuned_vs_default_geomean_speedup",
            "value": round(geo, 3), "unit": "x",
            "vs_baseline": round(max(speedups), 3),
            "specs": len(results),
            "kernels_with_win": kernels_with_win,
            "watchdog_silent": telemetry.watchdog_stats() == wd_before,
            "measurements": tune.status()["measurements"],
            "cache_path": tune.cache_path(),
            "rows": rows,
            "tpu_note": ("CPU interpret mode: Pallas kernels run through "
                         "the Pallas interpreter, so the XLA-native "
                         "candidate usually wins and the tuned tier's "
                         "speedup comes from routing around the "
                         "interpreted kernel — mechanism validation; "
                         "block-level TPU wins need hardware"
                         if on_cpu else None),
            "mfu": None}


def _use_bench_tune_cache():
    """Point the kernel benches' tuning cache at one fixed file beside the
    compile cache, started empty (``MXTPU_TUNE_CACHE`` set by the caller
    wins and is left as it is)."""
    if os.environ.get("MXTPU_TUNE_CACHE"):
        return
    from mxnet_tpu.context import compilation_cache_dir

    path = os.path.join(compilation_cache_dir(), "bench_tuning_cache.json")
    if os.path.exists(path):
        os.remove(path)
    os.environ["MXTPU_TUNE_CACHE"] = path


def bench_tune():
    """One offline tuning sweep over a small serving ladder: the workflow
    ``tools/tune_kernels.py`` automates, measured. Reports sweep wall
    time, entries persisted, and that a fresh in-process tier then
    resolves every ladder bucket without re-measuring."""
    from mxnet_tpu import tune

    small = os.environ.get("BENCH_KERNELS_SMALL", "") == "1"
    if default_backend_is_cpu():
        os.environ.setdefault("MXTPU_PALLAS_INTERPRET", "1")
    os.environ["MXTPU_TUNE"] = "1"
    _use_bench_tune_cache()
    tune.reset()
    specs = tune.ladder_specs(batch_ladder=(1, 2) if small else (1, 2, 4),
                              len_ladder=(64,) if small else (64, 128),
                              num_heads=2, head_dim=32, units=128,
                              families=("flash_fwd", "layer_norm"))
    t0 = time.perf_counter()
    results = tune.autotune(specs, trials=2, max_per_axis=2)
    sweep_s = time.perf_counter() - t0
    measured = tune.status()["measurements"]

    # fresh-process simulation: drop the in-process tier, preload from
    # disk, resolve every spec — zero additional measurements
    tune.reset()
    loaded = tune.preload()
    before = tune.status()
    for s in specs:
        cfg = tune.resolve(s["kernel"], tune.spec_key(s))
        assert cfg != "default"
    after = tune.status()
    return {"metric": "tune_sweep_wall_time", "value": round(sweep_s, 3),
            "unit": "s", "vs_baseline": 0.0,
            "specs": len(specs), "entries_persisted": loaded,
            "sweep_measurements": measured,
            "reload_measurements": after["measurements"] - measured,
            "reload_misses": after["misses"] - before["misses"],
            "cache_path": tune.cache_path(),
            "mfu": None}


def default_backend_is_cpu():
    from mxnet_tpu.context import default_backend

    return default_backend() == "cpu"


def main():
    which = (sys.argv[1] if len(sys.argv) > 1 else
             os.environ.get("BENCH", "resnet"))
    if which == "train_step" and "--shard-update" in sys.argv[2:]:
        which = "train_step_sharded"
    if which == "train_step" and "--shard-params" in sys.argv[2:]:
        which = "train_step_fsdp"
    if which == "train_step" and "--multi-step" in sys.argv[2:]:
        which = "train_step_multi"
        i = sys.argv.index("--multi-step")
        if len(sys.argv) > i + 1 and sys.argv[i + 1].isdigit():
            os.environ["BENCH_MULTI_STEP"] = sys.argv[i + 1]
    if which == "train_step" and "--mesh" in sys.argv[2:]:
        which = "train_step_tp"
        i = sys.argv.index("--mesh")
        if len(sys.argv) > i + 1:
            os.environ["BENCH_MESH"] = sys.argv[i + 1]
    if which == "serve_llm":
        argv = sys.argv[2:]
        if "--tp" in argv:
            i = sys.argv.index("--tp")
            if len(sys.argv) > i + 1 and sys.argv[i + 1].isdigit():
                os.environ["BENCH_SERVE_TP"] = sys.argv[i + 1]
        if "--speculate" in argv:
            i = sys.argv.index("--speculate")
            if len(sys.argv) > i + 1 and sys.argv[i + 1].isdigit():
                os.environ["BENCH_SPECULATE_K"] = sys.argv[i + 1]
        if "--prefix-shared" in argv:
            i = sys.argv.index("--prefix-shared")
            if len(sys.argv) > i + 1 and sys.argv[i + 1].isdigit():
                os.environ["BENCH_PREFIX_SHARED"] = sys.argv[i + 1]
        if "--paged" in argv:
            os.environ["BENCH_PAGED"] = "1"
    import functools

    result = {"metric": which, "value": 0.0, "unit": "",
              "vs_baseline": 0.0, "mfu": None}
    try:
        fn = {"resnet": bench_resnet_infer,
              "resnet_train": bench_resnet_train,
              "train_step": bench_train_step,
              "train_step_sharded": bench_train_step_sharded,
              "train_step_fsdp": bench_train_step_fsdp,
              "train_step_tp": bench_train_step_tp,
              "train_step_multi": bench_train_step_multi,
              "lstm_lm": bench_lstm_lm,
              "bert_pretrain": bench_bert_pretrain,
              "bert_large_pretrain": functools.partial(bench_bert_pretrain,
                                                       "large"),
              "optimizer_step": bench_optimizer_step,
              "telemetry_overhead": bench_telemetry_overhead,
              "serve": bench_serve,
              "serve_llm": bench_serve_llm,
              "checkpoint": bench_checkpoint,
              "tune": bench_tune,
              "kernels": bench_kernels}[which]
        from mxnet_tpu.context import default_backend

        backend = default_backend()
        result["backend"] = backend
        result["device"] = _device_info()[0]
        if backend != "tpu" and os.environ.get("BENCH_ALLOW_CPU", "") != "1":
            raise RuntimeError(
                f"no TPU here (backend {backend!r}); set BENCH_ALLOW_CPU=1 "
                "to measure the host anyway")
        result.update(fn())
    except BaseException as e:  # noqa: BLE001 — always emit the JSON line
        result["error"] = f"{type(e).__name__}: {e}"[:3500]
    print(json.dumps(result))
    sys.stdout.flush()
    if "error" in result:
        sys.exit(1)


if __name__ == "__main__":
    main()
