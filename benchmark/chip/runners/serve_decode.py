"""Serving through ``serve.DecodeEngine``: a closed loop that keeps every slot
busy. ``clients`` threads of this one process each send their next request
when the last one finished. The benchmark times requests itself, from the
client's side (``on_token``); ``eng.stats()`` gives counts only.

Requests come from the traffic generator: a fixed pool of (prompt, output)
lengths in this seed's order, handed out in turn to whichever client is
free. A ramp before the window lets the slots fall out of step, so the
window sees the steady state and not sixteen prefills at once.
"""
import os
import statistics
import threading
import time

import numpy as onp

from chipbench import reference, trace_reduce, traffic as gen


def percentile(values, q):
    """Nearest-rank percentile of a non-empty list."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


class Request:
    __slots__ = ("prompt", "want", "t_submit", "times", "tokens", "error",
                 "truncated", "expired")

    def __init__(self, prompt, want):
        self.prompt, self.want = prompt, want
        self.t_submit = None
        self.times, self.tokens = [], None
        self.error = None
        self.truncated = self.expired = False


def check_against_reference(task_mod, net, config, finished, pad_to):
    """Prompt plus generated tokens of each request go through the plain
    reference once; at every generated position the reference logit of the
    token the engine chose must lie near the reference maximum. Returns
    the largest gap seen, in units of that position's logit spread."""
    worst = 0.0
    for req in finished:
        seq = req.prompt + req.tokens
        row = onp.zeros((1, pad_to), dtype="int32")
        row[0, :len(seq)] = seq   # right padding: causal, so no effect
        logits = task_mod.reference_logits(net, config, row)[0]
        first = len(req.prompt) - 1
        at = logits[first:first + len(req.tokens)]
        worst = max([worst] + reference.chosen_token_gaps(at, req.tokens))
    return worst


def build_engine(net, mix):
    from mxnet_tpu.serve import DecodeEngine
    from mxnet_tpu.serve.decode import DecodePrograms

    e = mix["engine"]
    programs = DecodePrograms(
        net, num_slots=e["num_slots"], max_len=e["max_len"],
        prefill_batch=e["prefill_batch"],
        max_prompt_len=e["max_prompt_len"],
        min_prompt_bucket=e["min_prompt_bucket"],
        page_tokens=e["page_tokens"], speculate_k=1,
        prefix_cache=bool(e["prefix_cache"]))
    return DecodeEngine(programs=programs, deadline_ms=0,
                        max_queue=4 * int(mix["clients"]) + 16)


def run(config, traffic, seed, seconds, trace, env):
    import jax
    from jax.profiler import TraceAnnotation

    say, clog = env.say, env.compile_log
    spans = {}
    t0 = time.perf_counter()
    task_mod = env.load_task(config["task"])
    net = task_mod.build_net(config, seed)
    n_params = reference.n_params(net)
    spans["build_s"] = time.perf_counter() - t0
    pool = [Request(p, n) for p, n in
            gen.requests(traffic, seed, config["n_vocab"])]
    say(f"model {config['name']}: {n_params / 1e6:.1f}M parameters, "
        f"{config['dtype']}, built in {spans['build_s']:.1f}s; "
        f"{len(pool)} requests in the pool, prompts "
        f"{min(len(r.prompt) for r in pool)}-"
        f"{max(len(r.prompt) for r in pool)} "
        f"(mean {statistics.fmean(len(r.prompt) for r in pool):.0f}), "
        f"outputs {min(r.want for r in pool)}-{max(r.want for r in pool)} "
        f"(mean {statistics.fmean(r.want for r in pool):.0f})")

    mark = clog.mark()
    t0 = time.perf_counter()
    eng = build_engine(net, traffic)
    spans["engine_trace_s"] = time.perf_counter() - t0
    eng.warmup()
    spans["warmup_s"] = time.perf_counter() - t0
    built, hits, csec = clog.since(mark)
    compiled = eng.programs.compiled_programs()
    say(f"engine: traces {spans['engine_trace_s']:.1f}s, with warm-up of "
        f"{len(compiled)} programs {spans['warmup_s']:.1f}s; jax built or "
        f"loaded {built} programs ({hits} from the persistent cache) in "
        f"{csec:.1f}s of compile-or-load")

    state = {"next": 0, "stop": False}
    lock = threading.Lock()
    sent = []

    def client():
        while True:
            with lock:
                if state["stop"] or state["next"] >= len(pool):
                    return
                req = pool[state["next"]]
                state["next"] += 1
                sent.append(req)
            times = req.times
            with TraceAnnotation("bench:submit"):
                req.t_submit = time.perf_counter()
                stream = eng.submit(
                    req.prompt, max_new_tokens=req.want,
                    on_token=lambda _t: times.append(time.perf_counter()))
            try:
                with TraceAnnotation("bench:client_wait"):
                    req.tokens = stream.result(timeout=300)
            except Exception as e:  # noqa: BLE001 — counted as failed
                req.error = e
            req.truncated, req.expired = stream.truncated, stream.expired

    threads = [threading.Thread(target=client, name=f"client-{i}",
                                daemon=True)
               for i in range(int(traffic["clients"]))]
    pages_live = []
    sampler_stop = threading.Event()

    def sampler():
        while not sampler_stop.wait(1.0):
            pages_live.append(eng.stats()["kv_pages_live"])

    try:
        for t in threads:
            t.start()
        time.sleep(float(traffic["ramp_seconds"]))
        # the measured window
        trace_at, trace_len = 2.0, float(traffic["trace_seconds"])
        trace_dir = os.path.join(env.work_dir, "trace")
        mark = clog.mark()
        stats0 = eng.stats()
        threading.Thread(target=sampler, daemon=True).start()
        t_w0 = time.perf_counter()
        setup_s = t_w0 - env.t_start
        if trace:
            time.sleep(trace_at)
            trace_reduce.start(trace_dir)
            time.sleep(trace_len)
            trace_reduce.stop()
        time.sleep(max(0.0, t_w0 + seconds - time.perf_counter()))
        t_w1 = time.perf_counter()
        stats1 = eng.stats()
        sampler_stop.set()
        in_window = clog.since(mark)[0]
        with lock:
            state["stop"] = True
        for t in threads:
            t.join(timeout=300)
        stats_end = eng.stats()
    finally:
        eng.close()

    measured = [r for r in sent if t_w0 <= r.t_submit < t_w1]
    failed, good = [], []
    for r in measured:
        short = (r.error is not None or r.truncated or r.expired
                 or r.tokens is None or len(r.tokens) != r.want)
        (failed if short else good).append(r)
    ttft = [(r.times[0] - r.t_submit) * 1e3 for r in good]
    gaps, n_tokens = [], 0
    for r in sent:
        n_tokens += sum(t_w0 <= t < t_w1 for t in r.times)
        gaps += [(b - a) * 1e3 for a, b in zip(r.times, r.times[1:])
                 if t_w0 <= b < t_w1]
    window_s = t_w1 - t_w0
    tokens_per_s = n_tokens / window_s
    say(f"window {window_s:.2f}s: {len(measured)} requests submitted in it "
        f"({len(failed)} failed), {n_tokens} tokens, {tokens_per_s:.1f} "
        f"tokens/s, {len(measured) / window_s:.2f} requests/s; ttft over "
        f"{len(ttft)} samples, gaps over {len(gaps)}")
    end_to_end = {"serve_tokens_per_s": tokens_per_s, "setup_s": setup_s}
    if ttft and gaps:
        end_to_end["ttft_ms_p95"] = percentile(ttft, 95)
        end_to_end["itl_ms_p95"] = percentile(gaps, 95)
        say(f"ttft ms p50 {percentile(ttft, 50):.1f} p95 "
            f"{end_to_end['ttft_ms_p95']:.1f} max {max(ttft):.1f}; gap ms "
            f"p50 {percentile(gaps, 50):.1f} p95 "
            f"{end_to_end['itl_ms_p95']:.1f} max {max(gaps):.1f}")
    say(f"setup_s {setup_s:.1f} = build {spans['build_s']:.1f} + engine "
        f"traces {spans['engine_trace_s']:.1f} + compile-or-load of the "
        f"warm-up {spans['warmup_s'] - spans['engine_trace_s']:.1f} "
        f"({hits}/{built} from the cache) + ramp "
        f"{traffic['ramp_seconds']} + import and requests")

    mem = jax.devices()[0].memory_stats() or {}

    # correctness, outside the window: a seeded sample of finished requests
    # against the plain reference
    t0 = time.perf_counter()
    n_check = min(len(good), int(traffic["check_requests"]))
    picks = gen.rng(seed, 5).choice(len(good), size=n_check, replace=False) \
        if good else []
    tol = float(traffic["chosen_logit_tolerance"])
    worst = check_against_reference(
        task_mod, net, config, [good[i] for i in picks],
        int(traffic["check_pad_to"])) if n_check else None
    say(f"reference: {n_check} requests, largest gap of a chosen token "
        f"under the reference maximum {worst} of the logits' std "
        f"(tolerance {tol}), {time.perf_counter() - t0:.1f}s")
    shed = stats_end["shed"] + stats_end["evicted"] \
        + stats_end["page_starved"]
    checks = {
        "enough_requests_checked":
        n_check >= int(traffic["check_requests"]) or
        f"only {n_check} finished requests to check",
        "chosen_tokens_near_reference_maximum":
        (worst is not None and worst <= tol) or
        f"gap {worst} over tolerance {tol}",
        "every_request_complete": not failed or
        f"{len(failed)} of {len(measured)} requests failed or came short",
        "nothing_shed_or_evicted": shed == 0 or
        f"shed {stats_end['shed']} evicted {stats_end['evicted']} "
        f"starved {stats_end['page_starved']}",
        "no_compile_in_window": in_window == 0 or
        f"{in_window} programs compiled inside the window",
        "request_pool_lasted": state["next"] < len(pool) or
        f"all {len(pool)} requests were sent before the window ended",
    }

    summary = trace_reduce.reduce_dir(
        trace_dir, "unattributed (engine thread)") if trace else None
    ticks = stats1["ticks"] - stats0["ticks"]
    occupancy = None
    if ticks > 0:
        occupancy = (stats1["mean_slot_occupancy"] * stats1["ticks"]
                     - stats0["mean_slot_occupancy"] * stats0["ticks"]) \
            / ticks
    return {
        "attempted": len(measured), "failed": len(failed),
        "correct": all(v is True for v in checks.values()),
        "checks": checks, "end_to_end": end_to_end,
        "spans": dict(spans, window_s=window_s),
        "counters": {
            "ticks": ticks, "prefills": stats1["prefills"]
            - stats0["prefills"], "slot_occupancy": occupancy,
            "kv_pages": stats1["kv_pages"], "kv_pages_live": pages_live,
            "tokens_per_s": tokens_per_s, "n_params": n_params,
            "device_kind": jax.devices()[0].device_kind,
            "peak_bytes_in_use": mem.get("peak_bytes_in_use")},
        "trace": summary,
    }
