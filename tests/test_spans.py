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
TICK_SPANS = ["serve.tick.grow", "serve.tick.draft", "serve.tick.stage",
              "serve.tick.dispatch", "serve.tick.account",
              "serve.wait_tick", "serve.tick.commit"]
# what the two dispatch spans were until ISSUE 38: stage, call, account
TICK_CALL = ["serve.tick.stage", "serve.tick.dispatch",
             "serve.tick.account"]
PREFILL_CALL = ["serve.prefill.host", "serve.prefill.stage",
                "serve.prefill.dispatch", "serve.prefill.account"]
PREFILL_READ = ["serve.wait_prefill", "serve.prefill.commit"]
PROMPTS = ([1, 2, 3], [4, 5, 6, 7, 8], [9, 10])


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
def engine_k1(net):
    """One tick in flight: the wait reads the tick BEFORE the one just
    dispatched, and a row's token is accounted at dispatch."""
    eng = DecodeEngine(net, num_slots=4, max_len=MAX_LEN, max_prompt_len=16,
                       prefill_batch=2, page_tokens=8, speculate_k=1,
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


def _session(trace_dir, work):
    """``work()`` under one profiler session as the benchmark opens it:
    host tracer on, no Python call tracing. Returns (work's result, the
    host threads)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        out = work()
    finally:
        jax.profiler.stop_trace()
    return out, _host_threads(trace_dir)


def _thread_of(threads, name):
    return max(threads, key=lambda t: sum(n == name for n, *_ in t))


def _serve(engine):
    """Three requests through ``engine``; every compiled program notes the
    length of the list it is really called with."""
    handed = []
    programs = engine.programs._programs

    def noting(key, prog):
        def call(*args):
            handed.append((key[0], len(args)))
            return prog(*args)
        return call

    real = dict(programs)
    for key, prog in real.items():
        programs[key] = noting(key, prog)
    try:
        ticks0 = engine.stats()["ticks"]
        streams = [engine.submit(p, max_new_tokens=5) for p in PROMPTS]
        tokens = [s.result(timeout=120) for s in streams]
    finally:
        programs.update(real)
    return {"streams": streams, "tokens": tokens, "handed": handed,
            "ticks": engine.stats()["ticks"] - ticks0}


@pytest.fixture(scope="module")
def recorded(engine, step, tmp_path_factory):
    """One profiler session over three requests and three step calls."""
    x, y = _batch()
    step(x, y)                       # compiles outside the session
    with span("test.before_the_session"):
        pass

    def work():
        out = _serve(engine)
        out["losses"] = [float(step(x, y).asnumpy()) for _ in range(3)]
        return out

    out, threads = _session(str(tmp_path_factory.mktemp("spans_trace")),
                            work)
    return dict(out, threads=threads,
                engine=_thread_of(threads, "serve.tick.dispatch"),
                caller=_thread_of(threads, "train.dispatch"))


@pytest.fixture(scope="module")
def recorded_k1(engine_k1, tmp_path_factory):
    """The same three requests through the engine that keeps one tick in
    flight, in a session of its own."""
    out, threads = _session(str(tmp_path_factory.mktemp("spans_trace_k1")),
                            lambda: _serve(engine_k1))
    return dict(out, threads=threads,
                engine=_thread_of(threads, "serve.tick.dispatch"))


@pytest.fixture
def served(request):
    """(the recording, its engine's speculate_k) of either engine."""
    k = request.param
    return request.getfixturevalue("recorded" if k == 2
                                   else "recorded_k1"), k


BOTH_ENGINES = pytest.mark.parametrize("served", [2, 1], indirect=True,
                                       ids=["k2", "k1_in_flight"])


@pytest.mark.parametrize("recording,thread,family", [
    ("recorded", "engine", "serve."), ("recorded", "caller", "train."),
    ("recorded_k1", "engine", "serve.")])
def test_the_two_loops_emit_only_names_of_the_inventory(request, recording,
                                                       thread, family):
    rec = request.getfixturevalue(recording)
    names = {n for n, *_ in rec[thread]}
    assert names and names <= set(SPANS), names - set(SPANS)
    assert all(n.startswith(family) for n in names)
    if family == "serve.":
        assert set(TICK_CALL + PREFILL_CALL) <= names
    # nothing of the program is in the trace under another thread
    everything = {n for t in rec["threads"] for n, *_ in t}
    assert everything <= set(SPANS), everything - set(SPANS)


def test_a_step_call_is_these_spans_in_this_order(recorded):
    names = [n for n, *_ in recorded["caller"]]
    assert names == STEP_SPANS * 3
    assert all(onp.isfinite(v) for v in recorded["losses"])


def test_a_tick_is_these_spans_in_this_order(recorded):
    names = [n for n, *_ in recorded["engine"] if n in TICK_SPANS]
    assert len(names) >= 2 * len(TICK_SPANS)
    assert names == TICK_SPANS * (len(names) // len(TICK_SPANS))
    for stage in ("serve.admit.prepare", "serve.wait_prefill",
                  "serve.prefill.commit", "serve.expire", *PREFILL_CALL):
        assert any(n == stage for n, *_ in recorded["engine"]), stage
    assert all(len(t) == 5 for t in recorded["tokens"])


def test_a_tick_in_flight_is_read_after_the_next_went_out(recorded_k1):
    """speculate_k == 1: no draft; a tick is staged, called and accounted,
    and only then is the tick BEFORE it read back and committed. The last
    tick of a busy period is read with nothing behind it."""
    flight = [n for n in TICK_SPANS if n != "serve.tick.draft"]
    call, read = flight[:4], flight[4:]
    names = [n for n, *_ in recorded_k1["engine"] if n in TICK_SPANS]
    ticks = []
    for n in names:
        if n == flight[0]:
            ticks.append([])
        ticks[-1].append(n)
    assert len(ticks) == recorded_k1["ticks"] >= 5
    assert ticks[0] == call          # nothing before it to read
    for t in ticks[1:]:
        assert t[:4] == call and t[4:] in (read, read * 2), t
    assert names.count("serve.wait_tick") == len(ticks)
    assert all(len(t) == 5 for t in recorded_k1["tokens"])


@BOTH_ENGINES
def test_a_program_call_is_staged_called_and_accounted(served):
    """The six leaves tile what the two dispatch spans covered: around each
    call of a program its stage and its account, with nothing between."""
    rec, k = served
    names = [n for n, *_ in rec["engine"]]
    for first, order in (("serve.tick.stage", TICK_CALL),
                         ("serve.prefill.host", PREFILL_CALL)):
        at = [i for i, n in enumerate(names) if n == first]
        assert at
        for i in at:
            assert names[i:i + len(order)] == order
    # without a tick in flight a prefill is read at once; with one, only
    # after the tick that follows it went out
    after = [names[i + 1] for i, n in enumerate(names)
             if n == "serve.prefill.account"]
    reads = [names[i:i + 2] for i, n in enumerate(names)
             if n == PREFILL_READ[0]]
    assert reads and all(r == PREFILL_READ for r in reads)
    if k == 1:
        assert "serve.wait_prefill" not in after
        for j in (i for i, n in enumerate(names)
                  if n == "serve.wait_prefill"):
            since = names[:j][::-1].index("serve.prefill.account")
            assert "serve.tick.dispatch" in names[j - since:j]
    else:
        assert set(after) == {"serve.wait_prefill"}


@BOTH_ENGINES
def test_there_is_exactly_one_dispatch_span_a_tick(served):
    rec, _ = served
    names = [n for n, *_ in rec["engine"]]
    assert rec["ticks"] >= 3
    for leaf in TICK_CALL:
        assert names.count(leaf) == rec["ticks"], leaf
    prefills = names.count("serve.prefill.dispatch")
    assert 2 <= prefills <= 3       # three prompts, two a prefill at most
    assert names.count("serve.prefill.stage") == prefills
    assert names.count("serve.prefill.account") == prefills


@pytest.mark.parametrize("recording,thread", [
    ("recorded", "engine"), ("recorded", "caller"),
    ("recorded_k1", "engine")])
def test_spans_of_one_thread_are_flat_leaves(request, recording, thread):
    """No span encloses or overlaps another: a span's duration is its self
    time."""
    events = request.getfixturevalue(recording)[thread]
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


def _stats_by_name(events):
    by_name = {}
    for n, _, _, st in events:
        by_name.setdefault(n, []).append(st)
    return by_name


def test_attributes_known_inside_a_span_reach_the_event(recorded):
    by_name = _stats_by_name(recorded["engine"])
    assert sum(st["tokens"] for st in by_name["serve.tick.commit"]) >= 12
    assert all(st["live"] >= 1 for st in by_name["serve.tick.dispatch"])
    assert all("starved" in st for st in by_name["serve.admit.prepare"])
    taken = sum(st["n"] for n in ("serve.gather", "serve.wait_queue")
                for st in by_name.get(n, []))
    assert taken == 3


@BOTH_ENGINES
def test_the_leaves_carry_the_attributes_of_the_inventory(served):
    """``operands`` is the length of the list the executable was really
    called with; ``ended`` the requests the account ended (with a draft
    the commit decides that, after the read-back)."""
    rec, k = served
    by_name = _stats_by_name(rec["engine"])
    for kind, leaf in (("decode", "serve.tick.dispatch"),
                       ("prefill", "serve.prefill.dispatch")):
        handed = [n for key, n in rec["handed"] if key == kind]
        assert [st["operands"] for st in by_name[leaf]] == handed
        assert min(handed) > 20      # the parameter tail is most of it
    for leaf in TICK_CALL:
        assert all(1 <= st["live"] <= 3 for st in by_name[leaf]
                   if leaf != "serve.tick.account"), leaf
    ended = [st["ended"] for st in by_name["serve.tick.account"]]
    assert sum(ended) == (len(PROMPTS) if k == 1 else 0)
    for leaf in PREFILL_CALL[1:]:
        assert all(st["batch"] in (1, 2) for st in by_name[leaf]), leaf
    for leaf in PREFILL_CALL[1:3]:
        assert all(st["length"] >= 8 for st in by_name[leaf]), leaf


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


@pytest.mark.parametrize("which", ["engine", "engine_k1"])
def test_with_telemetry_on_the_old_timer_names_still_fill(request, which,
                                                          step):
    engine = request.getfixturevalue(which)
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
    # the leaves around the call are timers of their own names
    for leaf in TICK_CALL:
        assert tm.timer(leaf).count == ticks, leaf
    for leaf in PREFILL_CALL:
        assert tm.timer(leaf).count == 1, leaf
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
            "serve.tick.commit", "serve.tick.stage",
            "serve.tick.account"} <= {e["name"] for e in spans}


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
