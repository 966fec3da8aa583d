"""Training through ``Trainer.compile_step``: one compiled program a step.

The loop is the one a user writes: a fresh batch from the host every step,
the loss read back every ``log_every`` steps (as a loop that logs does) and
at the window's end, so the host may run ahead of the device in between.
The configuration's ``task`` says how the net, its batches, its loss and
its comparison with the plain reference are made.
"""
import math
import os
import statistics
import time

import numpy as onp

from chipbench import reference, trace_reduce


def _program_need(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def run(config, traffic, seed, seconds, trace, env):
    import jax
    from jax.profiler import TraceAnnotation

    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    say, clog = env.say, env.compile_log
    spans = {}
    t0 = time.perf_counter()
    task_mod = env.load_task(config["task"])
    task = task_mod.train(config, traffic, seed)
    n_params = reference.n_params(task.model)
    spans["build_s"] = time.perf_counter() - t0
    say(f"model {config['name']}: {n_params / 1e6:.1f}M parameters, "
        f"{config['dtype']}; batch {task.rows} x {task.length} = "
        f"{task.tokens_per_step} tokens a step; built in "
        f"{spans['build_s']:.1f}s")
    ring = task.batches(int(traffic["ring"]))

    # correctness, part 1, outside the window: inference-mode logits of a
    # seeded sample of rows against the plain reference
    t0 = time.perf_counter()
    rows = ring[0][0][:int(traffic["check_rows"])]
    err = reference.logits_error(
        task_mod.system_logits(task.model, rows),
        task_mod.reference_logits(task.model, config, rows))
    tol = float(config["logits_tolerance"])
    spans["reference_s"] = time.perf_counter() - t0
    say(f"reference: max |system - reference| logit = {err:.5f} of the "
        f"reference's std (tolerance {tol}) on {len(rows)} rows, "
        f"{spans['reference_s']:.1f}s")

    mesh_kw = {}
    if env.chips > 1:
        # a sharded cell: the configuration's ``mesh`` names the axes, the
        # task the partition rules (FSDP over dp, megatron over tp)
        from mxnet_tpu.parallel import make_mesh

        mesh_kw = {"mesh": make_mesh(dict(config["mesh"]),
                                     devices=jax.devices()[:env.chips]),
                   "shard_params": True,
                   "partition_rules": task_mod.partition_rules()}
    trainer = gluon.Trainer(task.net.collect_params(), traffic["optimizer"],
                            dict(traffic["optimizer_params"]))
    step = trainer.compile_step(task.net, task.loss_fn, **mesh_kw)
    if env.chips > 1 and step.shard_params is not True:
        raise RuntimeError("the parameters are not sharded: "
                           f"{step.shard_params_fallback_reason}")

    def do_step(i):
        x, y = ring[i % len(ring)]
        with TraceAnnotation("bench:batch_handover"):
            xs, ys = mx.np.array(x), mx.np.array(y)
        t = time.perf_counter()
        with TraceAnnotation("bench:step_call"):
            loss = step(xs, ys)
        return loss, time.perf_counter() - t

    def read(loss):
        with TraceAnnotation("bench:loss_wait"):
            loss._data.block_until_ready()
        return float(loss.asnumpy())

    # set-up: the first call traces, compiles or loads, and runs
    mark = clog.mark()
    t0 = time.perf_counter()
    losses = [read(do_step(0)[0])]
    spans["first_call_s"] = time.perf_counter() - t0
    built, hits, csec = clog.since(mark)
    say(f"first call {spans['first_call_s']:.1f}s: jax built or loaded "
        f"{built} programs ({hits} from the persistent cache) in "
        f"{csec:.1f}s of compile-or-load")
    for i in range(1, 1 + int(traffic["warm_steps"])):
        losses.append(read(do_step(i)[0]))
    compiled = next(iter(step.compiled_programs().values()), None)
    if compiled is not None:
        say(f"step program: tpu_custom_call="
            f"{compiled.as_text().count('tpu_custom_call')}, memory_analysis "
            f"need {_program_need(compiled) / 2**30:.2f} GiB")

    # the measured window
    log_every = int(traffic["log_every"])
    trace_at, trace_len = 2.0, float(traffic["trace_seconds"])
    trace_dir = os.path.join(env.work_dir, "trace")
    tracing, traced, profiler_s = False, False, 0.0
    mark = clog.mark()
    dispatch, n_steps, i = [], 0, len(losses)
    loss = None
    t_w0 = time.perf_counter()
    setup_s = t_w0 - env.t_start
    while True:
        now = time.perf_counter() - t_w0
        if now >= seconds:
            break
        if trace and not traced and not tracing and now >= trace_at:
            t = time.perf_counter()
            trace_reduce.start(trace_dir)
            profiler_s += time.perf_counter() - t
            tracing = True
        loss, dt = do_step(i)
        dispatch.append(dt)
        n_steps += 1
        i += 1
        if n_steps % log_every == 0:
            losses.append(read(loss))
        if tracing and time.perf_counter() - t_w0 >= trace_at + trace_len:
            read(loss)
            t = time.perf_counter()
            trace_reduce.stop()
            profiler_s += time.perf_counter() - t
            tracing, traced = False, True
    if loss is not None:
        losses.append(read(loss))
    window_s = time.perf_counter() - t_w0
    if tracing:
        trace_reduce.stop()
        traced = True
    summary = trace_reduce.reduce_dir(trace_dir) if traced else None
    in_window = clog.since(mark)[0]
    tokens_per_s = n_steps * task.tokens_per_step / window_s

    finite = all(math.isfinite(v) for v in losses)
    tail = statistics.fmean(losses[-5:])
    checks = {
        "logits_agree_with_reference": err <= tol or
        f"error {err:.5f} over tolerance {tol}",
        "losses_finite": finite or f"losses {losses}",
        "loss_fell": tail < losses[0] or
        f"mean of the last five {tail:.4f} not below the first "
        f"{losses[0]:.4f}",
        "compiled_step_in_use": step.fallback_reason is None or
        f"fell back: {step.fallback_reason}",
        "no_compile_in_window": in_window == 0 or
        f"{in_window} programs compiled inside the window",
    }
    say(f"window {window_s:.2f}s: {n_steps} steps, {tokens_per_s:.1f} "
        f"tokens/s; losses first {losses[0]:.4f}, last five "
        f"{' '.join(f'{v:.4f}' for v in losses[-5:])}; {len(losses)} read")
    say(f"setup_s {setup_s:.1f} = build {spans['build_s']:.1f} + reference "
        f"{spans['reference_s']:.1f} + first call "
        f"{spans['first_call_s']:.1f} (compile-or-load {csec:.1f}, "
        f"{hits}/{built} from the cache) + import, batches and warm steps")

    stats = jax.devices()[0].memory_stats() or {}
    return {
        "attempted": n_steps,
        "failed": 0 if finite else sum(not math.isfinite(v) for v in losses),
        "correct": all(v is True for v in checks.values()),
        "checks": checks,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "spans": dict(spans, host_dispatch_s=dispatch, window_s=window_s),
        "counters": {"steps": n_steps, "tokens_per_step":
                     task.tokens_per_step, "n_params": n_params,
                     # the profiler's own start and stop seconds taken out
                     "tokens_per_s": n_steps * task.tokens_per_step
                     / (window_s - profiler_s),
                     "profiler_s": profiler_s,
                     "device_kind": jax.devices()[0].device_kind,
                     "peak_bytes_in_use": stats.get("peak_bytes_in_use")},
        "trace": summary,
    }
