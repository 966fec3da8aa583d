"""The one span primitive (ISSUE 26): ``telemetry.span`` on the profiler's
clock at the phase boundaries of the decode engine's thread and of the
compiled step's call, the mechanisms folded onto it, and the stable names
of the compiled programs."""
import glob
import os
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, profiler, telemetry as tm
from mxnet_tpu.gluon.model_zoo import gpt_tiny
from mxnet_tpu.serve import DecodeEngine
from mxnet_tpu.telemetry import SPANS, span
from mxnet_tpu.telemetry.spans import PREFIX

VOCAB, MAX_LEN = 50, 64
STEP_SPANS = ["train.assemble", "train.key", "train.schedule",
              "train.dispatch", "train.writeback", "train.commit",
              "train.wait_health", "train.health", "train.mark"]
TICK_SPANS = ["serve.tick.grow", "serve.tick.draft", "serve.tick.dispatch",
              "serve.wait_tick", "serve.tick.commit"]


@pytest.fixture(autouse=True)
def clean_telemetry():
    tm.disable()
    tm.reset()
    yield
    tm.disable()
    tm.reset()


@pytest.fixture(scope="module")
def net():
    mx.random.seed(26)
    model = gpt_tiny(vocab_size=VOCAB, dropout=0.0, num_layers=2, units=32,
                     num_heads=4, max_length=MAX_LEN)
    model.initialize()
    return model


@pytest.fixture(scope="module")
def engine(net):
    eng = DecodeEngine(net, num_slots=4, max_len=MAX_LEN, max_prompt_len=16,
                       prefill_batch=2, page_tokens=8, speculate_k=2,
                       prefix_cache=False, cache_dir=False)
    eng.warmup()
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def step(net):
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    return trainer.compile_step(
        net, lambda out, y: loss(out.reshape(-1, VOCAB), y.reshape(-1)))


def _batch(seed=0):
    rs = onp.random.RandomState(seed)
    return (mx.np.array(rs.randint(0, VOCAB, (2, 16)).astype("int32")),
            mx.np.array(rs.randint(0, VOCAB, (2, 16)).astype("int32")))


def _host_threads(trace_dir):
    """[[(name, start_ns, end_ns, stats), ...] sorted, one list per host
    thread] of the ``mxtpu:`` events in the newest trace under the dir."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns,
                 ev.name[len(PREFIX):], {k: v for k, v in ev.stats})
                for ev in line.events if ev.name.startswith(PREFIX))
            if events:
                threads.append([(n, s, e, st) for s, e, n, st in events])
    return threads


@pytest.fixture(scope="module")
def recorded(engine, step, tmp_path_factory):
    """One profiler session over three requests and three step calls, as
    the benchmark opens it: host tracer on, no Python call tracing."""
    import jax

    trace_dir = str(tmp_path_factory.mktemp("spans_trace"))
    x, y = _batch()
    step(x, y)                       # compiles outside the session
    with span("test.before_the_session"):
        pass
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        streams = [engine.submit(p, max_new_tokens=5)
                   for p in ([1, 2, 3], [4, 5, 6, 7, 8], [9, 10])]
        tokens = [s.result(timeout=120) for s in streams]
        losses = [float(step(x, y).asnumpy()) for _ in range(3)]
    finally:
        jax.profiler.stop_trace()
    threads = _host_threads(trace_dir)

    def thread_of(name):
        return max(threads, key=lambda t: sum(n == name for n, *_ in t))

    return {"engine": thread_of("serve.tick.dispatch"),
            "caller": thread_of("train.dispatch"), "threads": threads,
            "streams": streams, "tokens": tokens, "losses": losses}


@pytest.mark.parametrize("thread,family", [("engine", "serve."),
                                           ("caller", "train.")])
def test_the_two_loops_emit_only_names_of_the_inventory(recorded, thread,
                                                       family):
    names = {n for n, *_ in recorded[thread]}
    assert names and names <= set(SPANS), names - set(SPANS)
    assert all(n.startswith(family) for n in names)
    # nothing of the program is in the trace under another thread
    everything = {n for t in recorded["threads"] for n, *_ in t}
    assert everything <= set(SPANS), everything - set(SPANS)


def test_a_step_call_is_these_spans_in_this_order(recorded):
    names = [n for n, *_ in recorded["caller"]]
    assert names == STEP_SPANS * 3
    assert all(onp.isfinite(v) for v in recorded["losses"])


def test_a_tick_is_these_spans_in_this_order(recorded):
    names = [n for n, *_ in recorded["engine"] if n in TICK_SPANS]
    assert len(names) >= 2 * len(TICK_SPANS)
    assert names == TICK_SPANS * (len(names) // len(TICK_SPANS))
    for stage in ("serve.admit.prepare", "serve.prefill.host",
                  "serve.prefill.dispatch", "serve.wait_prefill",
                  "serve.prefill.commit", "serve.expire"):
        assert any(n == stage for n, *_ in recorded["engine"]), stage
    assert all(len(t) == 5 for t in recorded["tokens"])


@pytest.mark.parametrize("thread", ["engine", "caller"])
def test_spans_of_one_thread_are_flat_leaves(recorded, thread):
    """No span encloses or overlaps another: a span's duration is its self
    time."""
    events = recorded[thread]
    for (_, _, end, _), (name, start, _, _) in zip(events, events[1:]):
        assert start >= end, name


def test_a_prefill_span_carries_the_ids_of_its_streams(recorded):
    hosts = [st for n, _, _, st in recorded["engine"]
             if n == "serve.prefill.host"]
    seen = [int(r) for st in hosts for r in str(st["rids"]).split()]
    assert sorted(seen) == sorted(s.rid for s in recorded["streams"])
    assert len({s.rid for s in recorded["streams"]}) == 3
    for st in hosts:
        assert st["batch"] >= 1 and st["length"] >= 8
        assert st["queue_wait_ms_max"] >= 0.0


def test_attributes_known_inside_a_span_reach_the_event(recorded):
    by_name = {}
    for n, _, _, st in recorded["engine"]:
        by_name.setdefault(n, []).append(st)
    assert sum(st["tokens"] for st in by_name["serve.tick.commit"]) >= 12
    assert all(st["live"] >= 1 for st in by_name["serve.tick.dispatch"])
    assert all("starved" in st for st in by_name["serve.admit.prepare"])
    taken = sum(st["n"] for n in ("serve.gather", "serve.wait_queue")
                for st in by_name.get(n, []))
    assert taken == 3


def test_without_a_session_a_span_leaves_no_event_and_costs_little(recorded):
    everything = {n for t in recorded["threads"] for n, *_ in t}
    assert "test.before_the_session" not in everything
    n = 20_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with span("test.cost", batch=1, length=2, live=3):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 5e-6, f"{best * 1e6:.2f} us a span with no session"
    assert tm.timer("test.cost").count == 0   # telemetry is off


def test_with_telemetry_on_the_old_timer_names_still_fill(engine, step):
    x, y = _batch(1)
    tm.enable()
    ticks0 = engine.stats()["ticks"]
    engine.submit([1, 2, 3], max_new_tokens=4).result(timeout=120)
    ticks = engine.stats()["ticks"] - ticks0
    for _ in range(2):
        step(x, y)
    # one sample a program call, the wait for its result included
    assert tm.timer("serve.decode_tick.call").count == ticks
    assert tm.timer("serve.prefill.call").count == 1
    assert tm.timer("train_step.call").count == 2
    assert tm.timer("serve.decode_tick.call").total >= \
        tm.timer("serve.tick.dispatch").total
    rows = tm.step_report()
    assert len(rows) == 2
    host = rows[-1]["host_time"]
    assert "train_step.call" in host
    # the dispatch span feeds train_step.call; the other phases have timers
    # of their own names beside it
    assert {"train.assemble", "train.wait_health", "train.mark"} <= set(host)
    assert "train.dispatch" not in host
    spans = [e for e in tm.events() if e.get("kind") == "span"]
    assert {"train_step.call", "serve.decode_tick.call",
            "serve.tick.commit"} <= {e["name"] for e in spans}


def test_program_timer_tells_a_compile_from_a_call():
    tm.enable()
    with tm.program_timer("demo") as sp:
        tm.record_compile("demo:site", (onp.zeros((2,)),))
    with tm.program_timer("demo"):
        pass
    assert sp.seconds > 0
    assert tm.timer("demo.compile").count == 1
    assert tm.timer("demo.call").count == 1


def test_after_makes_one_sample_of_two_spans():
    tm.enable()
    with span("demo.dispatch") as first:
        time.sleep(0.002)
    with span("demo.wait_result", timer="demo.call", after=first) as second:
        time.sleep(0.002)
    t = tm.timer("demo.call")
    assert t.count == 1
    assert t.total == pytest.approx(first.seconds + second.seconds)
    assert tm.timer("demo.dispatch").count == 1
    assert tm.timer("demo.wait_result").count == 0


def test_profiler_scope_is_a_span(tmp_path):
    import jax

    tm.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.scope("user_phase"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    assert tm.timer("profiler.user_phase").count == 1
    names = {n for t in _host_threads(str(tmp_path)) for n, *_ in t}
    assert names == {"user_phase"}


def test_a_request_has_one_identifier(engine):
    tm.enable()
    stream = engine.submit([3, 1, 4], max_new_tokens=2)
    stream.result(timeout=120)
    assert stream.trace is not None
    assert stream.trace.trace_id == stream.rid
    off = None
    tm.disable()
    off = engine.submit([3, 1, 4], max_new_tokens=2)
    off.result(timeout=120)
    assert off.trace is None and off.rid > stream.rid


@pytest.mark.parametrize("key,module", [
    (("decode", 2), "jit_mxtpu_serve_decode_k2"),
    (("prefill", 2, 8), "jit_mxtpu_serve_prefill_b2_t8"),
    (("prefill", 1, 16), "jit_mxtpu_serve_prefill_b1_t16"),
])
def test_serving_programs_have_stable_module_names(engine, key, module):
    text = engine.programs.compiled_programs()[key].as_text()
    assert text.startswith(f"HloModule {module},")


def test_the_step_program_names_its_module_and_its_phases(step):
    import re

    x, y = _batch()
    step(x, y)
    text = next(iter(step.compiled_programs().values())).as_text()
    assert text.startswith("HloModule jit_mxtpu_train_step,")
    ops = re.findall(r'op_name="([^"]*)"', text)
    assert any("/grad/" in n and "jvp(" in n for n in ops)
    assert any("/grad/" in n and "transpose(" in n for n in ops)
    assert any("/optimizer/" in n for n in ops)
    assert not any("/grad/" in n and "/optimizer/" in n for n in ops)
