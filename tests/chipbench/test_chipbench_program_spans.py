"""The readers of the program's own spans (``mxtpu:<name>``) on hand-built
traces whose answers are known, and on traces recorded on the CPU."""
import pytest

from chipbench_paths import BENCH
from chipbench import harness, program_spans as ps

US = 1000.0   # the trace's times are nanoseconds
NEW = ["step_host_ms.train", "step_blocked_ms.train",
       "engine_host_ms_per_tick.serve", "idle_attributed_share.train",
       "idle_attributed_share.serve"]
# one step call on the caller's thread, microseconds from its start: 23 us
# of host work around a 176 us wait for the device
STEP = [("train.assemble", 0, 4), ("train.key", 4, 6),
        ("train.schedule", 6, 8), ("train.dispatch", 8, 16),
        ("train.writeback", 16, 18), ("train.commit", 18, 19),
        ("train.wait_health", 19, 195), ("train.health", 195, 198),
        ("train.mark", 198, 199)]
# one tick on the engine's thread: 18 us of host work around an 81 us wait
TICK = [("serve.tick.grow", 0, 2), ("serve.tick.dispatch", 2, 10),
        ("serve.wait_tick", 10, 91), ("serve.tick.commit", 91, 97),
        ("serve.gather", 97, 98), ("serve.expire", 98, 99)]


def _events(template, base, packed=False):
    """Events of one step or tick; ``packed`` writes the attributes into
    the name, as some profilers do."""
    tail = "#live=3,starved=0#" if packed else ""
    return [("mxtpu:" + name + tail, (base + lo) * US, (hi - lo) * US)
            for name, lo, hi in template]


def _planes(ops, caller=(), other=()):
    host = [{"name": "python3", "events": list(caller)}]
    if other:
        host.append({"name": "python3", "events": list(other)})
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ("jit_mxtpu_x(1)", s, d) for _, s, d in ops]}]},
        {"name": "/host:CPU", "lines": host},
    ]


def train_planes():
    """Three whole step calls 200 us apart; the program of call i runs from
    its dispatch's end (+16) to the end of its wait (+195), so the device
    idles 21 us between two programs: 3 us under ``health``, 1 under
    ``mark``, 1 under no span of the program (the caller's own code between
    two calls) and 16 under the next call's spans up to its dispatch. The
    trace cut a call at each end."""
    ops = [("%fusion.1 = f32[8] fusion(f32[8] %p)", (200 * i + 16) * US,
            179 * US) for i in range(3)]
    caller = [("mxtpu:train.health", -5 * US, 3 * US),
              ("mxtpu:train.mark", -2 * US, 1 * US)]
    for i in range(3):
        caller += _events(STEP, 200 * i)
    caller += _events(STEP[:2], 600)
    caller.append(("bench:step_call", 0.0, 199 * US))
    return _planes(ops, caller)


def serve_planes(packed=False):
    """Three ticks 100 us apart; the tick's program runs from +10 to +90, so
    the device idles 20 us between two ticks, 1 us of it under no span. A
    second thread of the program emits a user's ``profiler.scope`` while
    the device is busy, and a client thread the benchmark's own span."""
    ops = [("%fusion.2 = f32[8] fusion(f32[8] %p)", (100 * i + 10) * US,
            80 * US) for i in range(3)]
    engine = []
    for i in range(3):
        engine += _events(TICK, 100 * i, packed)
    other = [("mxtpu:user_phase", 20 * US, 30 * US),
             ("bench:client_wait", 0.0, 300 * US)]
    return _planes(ops, engine, other)


@pytest.fixture
def reading_of(monkeypatch):
    """Make ``program_spans.reading`` give the reduction of these planes,
    as if the runner had just written them."""
    def use(planes):
        r = ps.reduce_planes(planes)
        monkeypatch.setattr(ps, "reading", lambda trace_dir=None: r)
        return r
    return use


def _read(name, obs):
    return harness.load_module(BENCH, "layer_metrics", name).read(obs)


SAW_A_DEVICE = {"trace": {"busy_s": 1.0, "window_s": 1.1}}


@pytest.mark.parametrize("name,want", [
    ("step_host_ms.train", 0.023),
    ("step_blocked_ms.train", 0.176),
    ("idle_attributed_share.train", 100 * 40 / 42),
])
def test_training_readers_on_a_known_trace(reading_of, name, want):
    r = reading_of(train_planes())
    assert r["steps"] == 3          # the two cut calls are left out
    assert _read(name, SAW_A_DEVICE) == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    ("engine_host_ms_per_tick.serve", 0.018),
    ("idle_attributed_share.serve", 100 * 38 / 40),
])
def test_serving_readers_on_a_known_trace(reading_of, name, want):
    r = reading_of(serve_planes())
    assert r["ticks"] == 3 and r["threads"] == 2
    assert _read(name, SAW_A_DEVICE) == pytest.approx(want)


def test_attributes_packed_into_a_name_are_stripped():
    assert ps.span_name("mxtpu:serve.tick.grow#live=3,starved=0#") \
        == "serve.tick.grow"
    plain, packed = (ps.reduce_planes(serve_planes(p))
                     for p in (False, True))
    assert packed["tick_ms"] == pytest.approx(plain["tick_ms"])
    assert packed["idle_split"] == pytest.approx(plain["idle_split"])


def test_a_gap_over_several_spans_splits_by_overlap():
    r = ps.reduce_planes(train_planes())
    assert r["idle_s"] == pytest.approx(42e-6)
    assert r["idle_split"] == pytest.approx({
        "train.health": 6e-6, "train.mark": 2e-6, ps.NO_SPAN: 2e-6,
        "train.assemble": 8e-6, "train.key": 4e-6, "train.schedule": 4e-6,
        "train.dispatch": 16e-6})
    assert sum(r["idle_split"].values()) == pytest.approx(r["idle_s"])
    # winner-takes-all would have given each whole gap to one span
    assert max(r["idle_split"].values()) < 0.5 * r["idle_s"]


def test_a_gap_under_no_span_lowers_the_attributed_share():
    planes = serve_planes()
    r0 = ps.reduce_planes(planes)
    # the engine thread's spans of the second tick are lost: the gaps on
    # both of its sides lose what lay under them
    planes[1]["lines"][0]["events"] = [
        ev for ev in planes[1]["lines"][0]["events"]
        if not 100 * US <= ev[1] < 200 * US]
    r1 = ps.reduce_planes(planes)
    assert r1["idle_s"] == pytest.approx(r0["idle_s"])
    assert r1["idle_split"][ps.NO_SPAN] == pytest.approx((1 + 10 + 10)
                                                         * 1e-6)
    assert r1["idle_attributed_share"] == pytest.approx(100 * 19 / 40)
    assert r1["idle_attributed_share"] < r0["idle_attributed_share"]


def test_per_step_and_per_tick_sums():
    r = ps.reduce_planes(train_planes())
    assert r["step_ms"]["train.wait_health"] == pytest.approx(0.176)
    assert r["step_ms"]["train.dispatch"] == pytest.approx(0.008)
    assert r["counts"]["train.mark"] == 4 and r["counts"]["train.key"] == 4
    assert r["seconds"]["train.dispatch"] == pytest.approx(24e-6)
    s = ps.reduce_planes(serve_planes())
    assert s["tick_ms"]["serve.wait_tick"] == pytest.approx(0.081)
    assert "user_phase" not in s["tick_ms"]       # another thread's span
    assert s["seconds"]["user_phase"] == pytest.approx(30e-6)
    assert s["steps"] == 0 and r["ticks"] == 0


def test_the_reading_is_printed_before_the_result_line():
    lines = []
    ps.report(ps.reduce_planes(train_planes()), lines.append)
    ps.report(ps.reduce_planes(serve_planes()), lines.append)
    text = "\n".join(lines)
    assert "train.dispatch 0.0000s (38.1%)" in text
    assert "(no span) 0.0000s (4.8%)" in text
    assert "median ms a step over 3 steps" in text
    assert "ms a tick over 3 ticks" in text and "serve.wait_tick 0.081" in text


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_reports_nothing(reading_of, name):
    """A commit from before the spans, traced with this benchmark: the
    readers return None and do not raise."""
    planes = train_planes()
    planes[1]["lines"] = [{"name": "python3", "events": [
        ("bench:step_call", 0.0, 199 * US)]}]
    r = reading_of(planes)
    assert r["threads"] == 0 and r["idle_attributed_share"] is None
    assert _read(name, SAW_A_DEVICE) is None
    lines = []
    ps.report(r, lines.append)
    assert lines == ["program spans: the trace holds no mxtpu: span"]


@pytest.mark.parametrize("name", NEW)
def test_a_run_that_saw_no_device_reports_nothing(reading_of, name):
    reading_of(train_planes())
    assert _read(name, {"trace": None}) is None
    assert _read(name, {}) is None


def test_no_trace_file_is_no_reading(tmp_path):
    assert ps.reading(str(tmp_path)) is None
    assert ps.default_trace_dir() == BENCH + "/.work/trace"


@pytest.mark.parametrize("workload,key,first", [
    ("gpt-tiny.train-tiny", "steps", "train.assemble"),
    ("gpt-tiny.decode-tiny", "ticks", "serve.tick.dispatch"),
])
def test_a_trace_recorded_on_the_cpu_loads(run_cell, tmp_path, capsys,
                                           workload, key, first):
    """The tiny cells under the runner's own profiler session: the file
    loads through ``program_spans`` and holds the program's spans, and for
    want of a device every reader still returns None."""
    from mxnet_tpu.telemetry import SPANS

    obs, found = run_cell(workload, seconds=4.0, trace=True)
    assert obs["trace"] is None
    r = ps.reading(str(tmp_path / "trace"))
    assert r is not None and r[key] >= 1, r
    assert r["counts"][first] >= r[key]
    assert set(r["counts"]) <= set(SPANS)
    assert r["idle_s"] is None and r["idle_attributed_share"] is None
    assert "program spans:" in capsys.readouterr().out
    assert ps.reading(str(tmp_path / "trace")) is r     # loaded once
    ours = {n: reader.read(obs) for n, reader in found["readers"].items()
            if n in NEW}
    assert len(ours) in (2, 3) and set(ours.values()) == {None}


def test_the_manifest_names_the_new_metrics(manifest):
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-5:] == NEW
    for name in NEW:
        m = by_name[name]
        assert m["source"] == "program_span"
        serve = name.endswith(".serve")
        assert m["moves"] == ("serve_tokens_per_s" if serve
                              else "train_tokens_per_s")
        assert len(m["workloads"]) == (1 if serve else 2)
        assert callable(harness.load_module(BENCH, "layer_metrics",
                                            name).read)
