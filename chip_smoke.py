#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mxnet_tpu still starts on the chip.

One process drives the two normal entry points on ``gluon.model_zoo.
gpt2_medium()`` at its published size (24 layers x 1024 units, 16 heads,
vocabulary 50257, context 1024; random weights from ``--seed``):

- train: ``gluon.Trainer(..., "adam")`` + ``trainer.compile_step(net, loss)``
  takes optimizer steps on 1024-token batches with buffer donation on; one
  batch is right-padded so the segment-id flash kernel runs. Every loss must
  be finite, the loss on a repeated batch must fall, and the step programs
  must contain ``tpu_custom_call`` (the Pallas kernels are on the path).
- serve: ``DecodeEngine(net, max_len=1024)`` is warmed up and answers
  concurrent greedy requests of different prompt lengths. The tokens are
  compared with ``net.generate(..., use_cache=False)`` and the compile count
  must not move after warm-up.

    python chip_smoke.py                  # one TPU chip; the driver's call
    python chip_smoke.py --chips 4        # ONLY the dp2 x tp2 sharded step
                                          #   and its one-device comparison,
                                          #   at full width and cut depth
    python chip_smoke.py --cpu-rehearsal  # sandbox: gpt_tiny on the CPU,
                                          #   Pallas interpreted; never "ok"

The last line of stdout is ``{"ok": true, "device": {...}}`` only when every
phase passed on a TPU whose kind is in ``telemetry.costs.PEAK_BF16``. Any
failure — no accelerator, a phase raising, an interpreted kernel, an unknown
device — exits non-zero and prints no result line. Timings printed on the
way are smoke readings around ``block_until_ready``, not a benchmark.
"""
import argparse
import gc
import json
import os
import sys
import time

REAL = dict(
    # 4 x 1024 also ran (0.20 s/step) but peaked at 15.70 of 15.75 GiB while
    # traces still held every activation of their eager forward; at two
    # rows the whole run peaks at 9.4 GiB
    model="gpt2_medium", vocab=50257, seq=1024, batch=2,
    pad_lengths=(1024, 333),
    steps=4, pad_steps=2, lr=3e-4,
    # 5 requests on 4 slots: the fifth joins when a slot retires
    num_slots=4, max_len=1024, max_prompt_len=32, prefill_batch=2,
    prompt_lens=(5, 9, 17, 12, 30), new_tokens=8, window=64,
    # --chips 4 cuts DEPTH, not width, unless --mesh-layers 24 lifts the
    # cut: the dp2 x tp2 FSDP step compiles slowly for four chips (2 layers:
    # 217 s on the four-chip host, 1185 s in the sandbox; 24 layers on one
    # chip: 150 s), and four chips are charged four times over
    mesh_layers=2, mesh_steps=3, mesh_lr=1e-4)
TINY = dict(
    model="gpt_tiny", vocab=512, seq=128, batch=4,
    pad_lengths=(128, 40),
    steps=4, pad_steps=2, lr=3e-3,
    num_slots=4, max_len=128, max_prompt_len=32, prefill_batch=2,
    prompt_lens=(5, 9, 17, 12, 30), new_tokens=8, window=64,
    mesh_layers=2, mesh_steps=3, mesh_lr=1e-3)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def say(msg):
    print(msg, flush=True)


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileLog:
    """Counts what jax compiled or fetched from the persistent cache."""

    def __init__(self):
        from jax import monitoring

        self.n = self.hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.n, self.hits, self.seconds)

    def since(self, mark):
        n, hits, s = mark
        return (f"{self.n - n} programs ({self.hits - hits} from the "
                f"persistent cache) in {self.seconds - s:.1f}s of "
                "compile-or-load")


def build_net(cfg, dropout=None, layers=None):
    """The model at its published size; ``layers`` cuts depth only."""
    from mxnet_tpu.gluon import model_zoo

    kw = {} if dropout is None else {"dropout": dropout}
    if cfg["model"] == "gpt2_medium" and layers is None:
        net = model_zoo.gpt2_medium(**kw)
    elif cfg["model"] == "gpt2_medium":
        net = model_zoo.GPTModel(vocab_size=cfg["vocab"], num_layers=layers,
                                 units=1024, num_heads=16, **kw)
    else:
        # rehearsal: head_dim 64 and a 128-wide LayerNorm, so the same
        # kernels are reached (interpreted) as on the chip
        net = model_zoo.gpt_tiny(vocab_size=cfg["vocab"], units=128,
                                 num_heads=2, num_layers=layers or 2,
                                 max_length=cfg["seq"], **kw)
    net.initialize()
    n_params = sum(int(p.data().size) for p in net.collect_params().values())
    say(f"model {cfg['model']}: {net._num_layers} layers x {net._units} "
        f"units, {net._num_heads} heads, vocab {net.vocab_size}, context "
        f"{net.max_length}, {n_params / 1e6:.1f}M parameters, "
        f"{net._dtype}")
    if cfg["model"] == "gpt2_medium":
        check((net._num_layers, net._units, net._num_heads, net.vocab_size,
               net.max_length) == (layers or 24, 1024, 16, 50257, 1024),
              "gpt2_medium is not at its published size")
    return net


def token_batch(rs, cfg, lengths=None):
    """(inputs, labels) int32 of shape (rows, seq): next-token pairs over
    random tokens, ``batch`` rows or one per entry of ``lengths``. With
    ``lengths`` the INPUT rows are right-padded with the last id; the
    labels stay random, so nothing teaches the model to answer with the
    pad id."""
    import numpy as onp

    pad_id = cfg["vocab"] - 1
    rows = cfg["batch"] if lengths is None else len(lengths)
    toks = rs.randint(0, pad_id, size=(rows, cfg["seq"] + 1))
    x = onp.ascontiguousarray(toks[:, :-1], dtype="int32")
    y = onp.ascontiguousarray(toks[:, 1:], dtype="int32")
    for row, n in enumerate(lengths or ()):
        x[row, n:] = pad_id
    return x, y


def hbm_in_use(label):
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_in_use" in stats:
        say(f"{label}: HBM in use {stats['bytes_in_use'] / 2**30:.2f} GiB, "
            f"peak so far {stats['peak_bytes_in_use'] / 2**30:.2f} GiB")


def run_steps(step, x, y, n, label):
    """n optimizer steps on one repeated batch; returns the losses."""
    import numpy as onp

    import mxnet_tpu as mx

    xs, ys = mx.np.array(x), mx.np.array(y)
    losses, times = [], []
    for i in range(n):
        t0 = time.perf_counter()
        loss = step(xs, ys)
        loss._data.block_until_ready()
        times.append(time.perf_counter() - t0)
        losses.append(float(loss.asnumpy()))
        check(onp.isfinite(losses[-1]), f"{label}: loss {losses[-1]} at "
                                        f"step {i} is not finite")
    say(f"{label}: losses {' '.join(f'{v:.4f}' for v in losses)}")
    say(f"{label}: first call {times[0]:.1f}s (trace + compile or cache "
        f"load + run); later steps "
        f"{' '.join(f'{t:.3f}' for t in times[1:])} s (smoke reading)")
    check(step.fallback_reason is None,
          f"{label}: compiled step fell back to eager: "
          f"{step.fallback_reason}")
    return losses


def report_step_programs(step, label, on_tpu, hbm_limit):
    from mxnet_tpu.train_step import train_donate_argnums

    check(train_donate_argnums() == (0, 1) or not on_tpu,
          "buffer donation is off on the chip")
    progs = step.compiled_programs()
    check(progs, f"{label}: no compiled step program")
    for sig, compiled in progs.items():
        n_kernels = compiled.as_text().count("tpu_custom_call")
        ma = compiled.memory_analysis()
        need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        say(f"{label}: program x{sig[0]} tpu_custom_call={n_kernels} "
            f"memory_analysis: arguments {ma.argument_size_in_bytes / 2**30:.2f}"
            f" GiB, outputs {ma.output_size_in_bytes / 2**30:.2f} GiB, "
            f"aliased (donated) {ma.alias_size_in_bytes / 2**30:.2f} GiB, "
            f"temporaries {ma.temp_size_in_bytes / 2**30:.2f} GiB, "
            f"needs {need / 2**30:.2f} GiB")
        if on_tpu:
            check(n_kernels > 0, f"{label}: no tpu_custom_call in the step "
                                 "program — the Pallas kernels were bypassed")
            check(ma.alias_size_in_bytes > 0,
                  f"{label}: nothing is donated in the step program")
            check(hbm_limit and need < hbm_limit,
                  f"{label}: program needs {need} bytes, HBM holds "
                  f"{hbm_limit}")


def train_phase(cfg, net, rs, on_tpu, hbm_limit, clog):
    from mxnet_tpu import gluon

    class PaddedLM(gluon.HybridBlock):
        """Right-padded batches: the valid length of each row is read off
        the pad id, as a data pipeline would hand it over."""

        def __init__(self, lm, pad_id):
            super().__init__()
            self.lm = lm
            self._pad_id = pad_id

        def forward(self, tokens):
            valid = (tokens != self._pad_id).astype("int32").sum(axis=1)
            return self.lm(tokens, valid)

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": cfg["lr"]})
    mark = clog.mark()
    step = trainer.compile_step(net, loss_fn)
    x, y = token_batch(rs, cfg)
    losses = run_steps(step, x, y, cfg["steps"], "train")
    check(losses[-1] < losses[0],
          f"train: loss did not fall on a repeated batch: {losses}")
    report_step_programs(step, "train", on_tpu, hbm_limit)
    hbm_in_use("train")

    # the padded batch reaches flash attention with segment ids
    padded = trainer.compile_step(PaddedLM(net, cfg["vocab"] - 1), loss_fn)
    xp, yp = token_batch(rs, cfg, cfg["pad_lengths"])
    plosses = run_steps(padded, xp, yp, cfg["pad_steps"], "train-padded")
    check(plosses[-1] < plosses[0],
          f"train-padded: loss did not fall on a repeated batch: {plosses}")
    report_step_programs(padded, "train-padded", on_tpu, hbm_limit)
    hbm_in_use("train-padded")
    say(f"train: jax built {clog.since(mark)}")


def serve_phase(cfg, net, rs, on_tpu, clog):
    from mxnet_tpu import telemetry
    from mxnet_tpu.serve import DecodeEngine

    mark = clog.mark()
    t0 = time.perf_counter()
    eng = DecodeEngine(net, num_slots=cfg["num_slots"],
                       max_len=cfg["max_len"],
                       max_prompt_len=cfg["max_prompt_len"],
                       prefill_batch=cfg["prefill_batch"],
                       prefix_cache=False)
    try:
        eng.warmup()
        say(f"serve: warm-up of {len(eng.programs.compiled_programs())} "
            f"programs took {time.perf_counter() - t0:.1f}s; jax built "
            f"{clog.since(mark)}")
        n_kernels = {key: c.as_text().count("tpu_custom_call")
                     for key, c in eng.programs.compiled_programs().items()}
        say("serve: tpu_custom_call per program: " + ", ".join(
            f"{'|'.join(map(str, k))}={v}" for k, v in sorted(
                n_kernels.items(), key=str)))
        if on_tpu:
            check(all(v > 0 for k, v in n_kernels.items()
                      if k[0] == "prefill"),
                  "serve: a prefill program holds no tpu_custom_call")
        prompts = [[int(t) for t in rs.randint(0, cfg["vocab"] - 1, size=n)]
                   for n in cfg["prompt_lens"]]
        telemetry.enable()
        compiles0, jax0 = telemetry.compile_count(), clog.n
        t0 = time.perf_counter()
        streams = [eng.submit(p, max_new_tokens=cfg["new_tokens"])
                   for p in prompts]
        got = [s.result(timeout=600) for s in streams]
        wall = time.perf_counter() - t0
        moved = (telemetry.compile_count() - compiles0, clog.n - jax0)
        telemetry.disable()
        st = eng.stats()
        say(f"serve: {len(prompts)} concurrent requests, prompt lengths "
            f"{list(cfg['prompt_lens'])}, {st['tokens']} tokens in "
            f"{st['ticks']} ticks + {st['prefills']} prefills, {wall:.2f}s "
            f"wall, tick p50 {st['tpot_ms_p50']:.1f} ms (smoke reading)")
        check(all(len(g) == cfg["new_tokens"] for g in got),
              f"serve: short answers {[len(g) for g in got]}")
        check(moved == (0, 0),
              f"serve: compiles after warm-up moved by {moved} "
              "(framework traces, jax programs)")
        say("serve: compiles after warm-up: 0")
        hbm_in_use("serve")
    finally:
        eng.close()
    matched = 0
    for p, g in zip(prompts, got):
        ref = net.generate(p, max_new_tokens=cfg["new_tokens"],
                           temperature=0.0, use_cache=False,
                           window=cfg["window"])[len(p):]
        same = [int(t) for t in ref] == g
        matched += same
        say(f"serve: prompt of {len(p):2d} -> {g} "
            f"{'== uncached greedy' if same else f'!= uncached {ref}'}")
    check(matched >= 1, "serve: no request matches the uncached greedy "
                        "reference")
    say(f"serve: {matched}/{len(prompts)} requests equal "
        "generate(use_cache=False)")


def mesh_phase(cfg, devices, on_tpu, clog):
    """The dp2 x tp2 sharded step against the same steps on one device."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.gluon.model_zoo.gpt import gpt_tp_rules
    from mxnet_tpu.parallel import make_mesh

    rs = onp.random.RandomState(cfg["seed"])
    x, y = token_batch(rs, cfg)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def bytes_in_use():
        return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                for d in devices]

    def run(mesh):
        mx.random.seed(cfg["seed"])
        net = build_net(cfg, dropout=0.0, layers=cfg["mesh_layers"])
        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": cfg["mesh_lr"]})
        if mesh is None:
            step = trainer.compile_step(net, loss_fn)
            label = "one-device"
        else:
            step = trainer.compile_step(
                net, loss_fn, mesh=mesh, shard_params=True,
                partition_rules=gpt_tp_rules("train"))
            label = "dp2xtp2"
        losses = run_steps(step, x, y, cfg["mesh_steps"], label)
        return step, losses

    base = bytes_in_use()
    mark = clog.mark()
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=devices)
    step, sharded = run(mesh)
    check(step.shard_params is True,
          f"dp2xtp2: parameters are not sharded: "
          f"{step.shard_params_fallback_reason}")
    per = telemetry.gauge("train_step.param_bytes_per_replica").value
    rep = telemetry.gauge("train_step.param_bytes_replicated").value
    say(f"dp2xtp2: param_bytes_per_replica {int(per)} of replicated "
        f"{int(rep)} ({per / rep:.3f})")
    check(0 < per < rep, "dp2xtp2: a replica holds the whole model")
    text = next(iter(step.compiled_programs().values())).as_text()
    say("dp2xtp2: collectives in the step program: " + ", ".join(
        f"{op}={text.count(op + '(') + text.count(op + '-start(')}"
        for op in ("all-gather", "reduce-scatter", "all-reduce",
                   "collective-permute")))
    held = bytes_in_use()
    say("dp2xtp2: bytes_in_use per device after the steps: "
        + " ".join(str(b) for b in held)
        + " (before: " + " ".join(str(b) for b in base) + ")")
    if on_tpu:
        check(min(held) > 0.5 * max(held),
              f"dp2xtp2: the devices do not hold like shares: {held}")
        check(min(held) > per,
              f"dp2xtp2: a device holds less than its parameter share")
        check("tpu_custom_call" in text,
              "dp2xtp2: no tpu_custom_call in the sharded step program")
    say(f"dp2xtp2: jax built {clog.since(mark)}")
    del step
    gc.collect()

    _, single = run(None)
    diff = onp.abs(onp.array(sharded) - onp.array(single))
    say(f"dp2xtp2 vs one-device: max |loss difference| {diff.max():.3e}")
    check(onp.allclose(sharded, single, rtol=1e-4, atol=1e-5),
          f"dp2xtp2 losses {sharded} leave the one-device losses {single}")
    check(sharded[-1] < sharded[0], "dp2xtp2: loss did not fall")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp2 x tp2 sharded step and its "
                         "one-device comparison")
    ap.add_argument("--mesh-layers", type=int, default=None,
                    help="depth of the --chips 4 model (default 2; 24 is "
                         "GPT-2 medium's own)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="sandbox rehearsal on the CPU at gpt_tiny with "
                         "Pallas interpreted; never prints ok: true")
    args = ap.parse_args()

    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["MXTPU_PALLAS_INTERPRET"] = "1"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}")
    t_start = time.perf_counter()
    import jax
    import numpy as onp

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not args.cpu_rehearsal:
        check(on_tpu, f"no TPU: jax found {device}")
        check(os.environ.get("MXTPU_PALLAS_INTERPRET", "") != "1",
              "MXTPU_PALLAS_INTERPRET=1: the kernels would run interpreted")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs that many devices, jax has "
          f"{len(devices)}")

    import mxnet_tpu as mx
    from mxnet_tpu.telemetry.costs import table_peak_flops

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    say(f"jax {jax.__version__}, libtpu {libtpu_version}, device "
        f"{device['kind']} x{device['count']} ({device['platform']})")
    if on_tpu:
        check(table_peak_flops(device["kind"]) is not None,
              f"device kind {device['kind']!r} is not in "
              "telemetry.costs.PEAK_BF16")
    cache_dir = mx.context.enable_compilation_cache()
    files = [os.path.join(cache_dir, f) for f in os.listdir(cache_dir)] \
        if os.path.isdir(cache_dir) else []
    placed = "placed by JAX_COMPILATION_CACHE_DIR" \
        if os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        else "in-checkout default"
    # a size cap makes jax evict least-recently-used entries: this model's
    # programs are large enough for one run to evict its own earlier ones
    say(f"compile cache at {cache_dir} ({placed}): {len(files)} files, "
        f"{sum(map(os.path.getsize, files)) / 2**20:.1f} MiB at start; "
        f"size cap {jax.config.jax_compilation_cache_max_size} bytes "
        "(-1: none)")
    hbm_limit = (devices[0].memory_stats() or {}).get("bytes_limit")

    cfg = dict(TINY if args.cpu_rehearsal else REAL, seed=args.seed)
    if args.mesh_layers:
        cfg["mesh_layers"] = args.mesh_layers
    clog = CompileLog()
    if args.chips == 4:
        mesh_phase(cfg, devices[:4], on_tpu, clog)
        device["count"] = 4
    else:
        mx.random.seed(args.seed)
        rs = onp.random.RandomState(args.seed)
        net = build_net(cfg)
        train_phase(cfg, net, rs, on_tpu, hbm_limit, clog)
        # the trainer and its Adam state went out of scope with the phase;
        # the engine's traces need the room (they too run eagerly)
        gc.collect()
        hbm_in_use("before serve")
        serve_phase(cfg, net, rs, on_tpu, clog)
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        say(f"device {d.id}: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use')} of bytes_limit "
            f"{stats.get('bytes_limit')}")
    say(f"all phases passed in {time.perf_counter() - t_start:.0f}s "
        "(smoke reading, compilation included)")
    if args.cpu_rehearsal:
        # a rehearsal proves control flow, never the chip
        say(json.dumps({"ok": False, "rehearsal": "cpu", "device": device}))
        return 0
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
