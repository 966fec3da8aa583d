"""The join of the device's program runs with the program's own spans
(``chipbench/launch_spans.py``) on hand-built traces whose answers are known,
its seven readers, and a trace recorded on the CPU."""
import pytest

from chipbench_paths import BENCH
from chipbench import harness, launch_spans as ls, program_spans as ps

US = 1000.0   # the trace's times are nanoseconds
ENGINE = ["engine_stage_ms_per_tick.serve", "engine_launch_ms_per_tick.serve",
          "engine_account_ms_per_tick.serve"]
JOINED = ["launch_exposed_ms.serve", "readback_ms.serve",
          "launch_exposed_ms.train", "readback_ms.train"]
NEW = ENGINE + JOINED
TICK_RUN = "jit_mxtpu_serve_decode_k1(7)"
PREFILL_RUN = "jit_mxtpu_serve_prefill_b1_t128(9)"
STEP_RUN = "jit_mxtpu_train_step(3)"
# one pass of the engine's loop, microseconds from its start, where the HOST
# is the slower side: the device has long finished the tick before when the
# call begins (+20) and the wait (+60) reads a result that is there already
HOST_BOUND = [("serve.tick.grow", 0, 10), ("serve.tick.stage", 10, 20),
              ("serve.tick.dispatch", 20, 50), ("serve.tick.account", 50, 60),
              ("serve.wait_tick", 60, 70), ("serve.tick.commit", 70, 95),
              ("serve.gather", 95, 97), ("serve.expire", 97, 99)]
# and where the DEVICE is: the call is out at +4 while the tick before still
# runs until +75, and the wait sits on it from +14
DEVICE_BOUND = [("serve.tick.grow", 0, 2), ("serve.tick.stage", 2, 4),
                ("serve.tick.dispatch", 4, 12), ("serve.tick.account", 12, 14),
                ("serve.wait_tick", 14, 78), ("serve.tick.commit", 78, 90),
                ("serve.gather", 90, 91), ("serve.expire", 91, 92)]
# one step call whose first wait is the loss scaler's overflow flag
STEP = [("train.assemble", 0, 4), ("train.key", 4, 6),
        ("train.schedule", 6, 8), ("train.dispatch", 8, 16),
        ("train.writeback", 16, 18), ("train.wait_overflow", 18, 150),
        ("train.commit", 150, 151), ("train.wait_health", 151, 160),
        ("train.health", 160, 163), ("train.mark", 163, 164)]


def _events(template, base):
    return [("mxtpu:" + name, (base + lo) * US, (hi - lo) * US)
            for name, lo, hi in template]


def _planes(runs, thread, clients=()):
    """``runs``: (module name, start us, end us) on the one device, each one
    operation long; ``thread``: the engine's or the caller's events."""
    modules = [(name, lo * US, (hi - lo) * US) for name, lo, hi in runs]
    ops = [(f"%fusion.{i} = f32[8] fusion(f32[8] %p)", s, d)
           for i, (_, s, d) in enumerate(modules)]
    host = [{"name": "python3", "events": list(thread)}]
    if clients:
        host.append({"name": "python3", "events": list(clients)})
    return [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": host}]


def host_bound_planes(ticks=4, template=HOST_BOUND):
    """Ticks 100 us apart; tick i runs from +40, 20 us after its call began,
    to +75: the device idles 65 us between two ticks, 20 of them with the
    call already begun. The wait of pass i (+60..+70) reads tick i - 1,
    done 85 us before: all 10 us of it are the way back."""
    runs = [(TICK_RUN, 100 * i + 40, 100 * i + 75) for i in range(ticks)]
    thread = [ev for i in range(ticks)
              for ev in _events(template, 100 * i)]
    clients = [("bench:client_wait", 0.0, 100 * ticks * US)]
    return _planes(runs, thread, clients)


def device_bound_planes(ticks=4):
    """Ticks 100 us apart; tick i runs from +80 to +175, so two ticks are
    5 us apart on the device (no idle gap as ``trace_reduce`` counts them)
    and the wait of pass i (+14..+78) ends 3 us after tick i - 1 did."""
    runs = [(TICK_RUN, 100 * i - 20, 100 * i + 75) for i in range(ticks + 1)]
    thread = [ev for i in range(ticks)
              for ev in _events(DEVICE_BOUND, 100 * i)]
    return _planes(runs, thread)


def train_planes(steps=3, template=STEP):
    """Step calls 200 us apart; the step runs from +14, 6 us after its call
    began, to +147, 3 us before the first wait ends."""
    runs = [(STEP_RUN, 200 * i + 14, 200 * i + 147) for i in range(steps)]
    thread = [ev for i in range(steps) for ev in _events(template, 200 * i)]
    return _planes(runs, thread)


def _reduce(planes):
    return ls.reduce_planes(planes, ps.reduce_planes(planes)["tick_ms"])


def _flat(intervals):
    return [t for iv in intervals for t in iv]


@pytest.fixture
def reading_of(monkeypatch):
    """Make both modules' ``reading`` give the reduction of these planes, as
    if the runner had just written them."""
    def use(planes):
        spans = ps.reduce_planes(planes)
        r = ls.reduce_planes(planes, spans["tick_ms"])
        monkeypatch.setattr(ps, "reading", lambda trace_dir=None: spans)
        monkeypatch.setattr(ls, "reading", lambda trace_dir=None: r)
        return r
    return use


def _read(name, obs):
    return harness.load_module(BENCH, "layer_metrics", name).read(obs)


SAW_A_DEVICE = {"trace": {"busy_s": 1.0, "window_s": 1.1}}


@pytest.mark.parametrize("planes,launch_us,readback_us", [
    # the device free before the call: run start - span start; the wait
    # began after its run ended: all of the wait
    (host_bound_planes, 20.0, 10.0),
    # the device busy past the call: the gap between two programs; the wait
    # began before its run ended: what is left of it after the run
    (device_bound_planes, 5.0, 3.0),
])
def test_the_join_on_a_known_serving_trace(reading_of, planes, launch_us,
                                           readback_us):
    r = reading_of(planes())
    tick = r["kinds"]["tick"]
    assert [hi - lo for lo, hi in tick["launches"]] \
        == pytest.approx([launch_us * US] * len(tick["launches"]))
    assert len(tick["launches"]) == 4
    # the first pass's wait read a tick from before the trace
    back = [hi - lo for lo, hi in tick["readbacks"]]
    assert back == pytest.approx([readback_us * US] * len(back))
    assert len(back) == (3 if planes is host_bound_planes else 4)
    assert _read("launch_exposed_ms.serve", SAW_A_DEVICE) \
        == pytest.approx(launch_us / 1e3)
    assert _read("readback_ms.serve", SAW_A_DEVICE) \
        == pytest.approx(readback_us / 1e3)
    assert _read("launch_exposed_ms.train", SAW_A_DEVICE) is None
    assert _read("readback_ms.train", SAW_A_DEVICE) is None


@pytest.mark.parametrize("template,readback_us", [
    (STEP, 3.0),                               # wait_overflow, then health
    ([s for s in STEP if s[0] != "train.wait_overflow"], 9.0),
])
def test_the_join_on_a_known_training_trace(reading_of, template,
                                            readback_us):
    """A step call's FIRST wait is the one that waits for the program: the
    health read-back after an overflow read-back finds the result there."""
    r = reading_of(train_planes(template=template))
    step = r["kinds"]["step"]
    assert step["runs"] == 3 and len(step["readbacks"]) == 3
    assert _read("launch_exposed_ms.train", SAW_A_DEVICE) \
        == pytest.approx(0.006)
    assert _read("readback_ms.train", SAW_A_DEVICE) \
        == pytest.approx(readback_us / 1e3)
    assert _read("launch_exposed_ms.serve", SAW_A_DEVICE) is None
    for name in ENGINE:
        assert _read(name, SAW_A_DEVICE) is None


def test_a_run_whose_span_the_trace_cut_is_left_out():
    planes = host_bound_planes()
    # the profiler started inside the first pass's call: its spans up to
    # the dispatch are not in the trace, its run is
    planes[1]["lines"][0]["events"] = [
        ev for ev in planes[1]["lines"][0]["events"] if ev[1] >= 50 * US]
    tick = _reduce(planes)["kinds"]["tick"]
    assert tick["runs"] == 4 and len(tick["launches"]) == 3
    assert tick["launches"][0][1] == pytest.approx(140 * US)
    # ... and stopped inside the last: a fifth run with no span of its own
    # does not take the fourth's
    planes[0]["lines"][1]["events"].append((TICK_RUN, 380 * US, 10 * US))
    again = _reduce(planes)["kinds"]["tick"]
    assert again["runs"] == 5
    assert _flat(again["launches"]) == pytest.approx(_flat(tick["launches"]))


def test_two_prefills_out_at_once_each_find_their_own_span():
    """Both calls return while the device is still busy with a tick, and
    both runs start after the second call began: the first run is the first
    span's, not the latest's."""
    runs = [(TICK_RUN, 0, 100), (PREFILL_RUN, 102, 150),
            (PREFILL_RUN, 151, 200)]
    thread = [("mxtpu:serve.prefill.dispatch", 10 * US, 10 * US),
              ("mxtpu:serve.prefill.dispatch", 30 * US, 10 * US),
              ("mxtpu:serve.tick.dispatch", 50 * US, 10 * US),
              ("mxtpu:serve.wait_prefill", 60 * US, 145 * US),
              ("mxtpu:serve.wait_prefill", 206 * US, 4 * US)]
    pre = _reduce(_planes(runs, thread))["kinds"]["prefill"]
    assert _flat(pre["launches"]) == pytest.approx(
        [100 * US, 102 * US, 150 * US, 151 * US])
    # the first wait ended after both runs: it read the first, the second
    # wait the second, which had ended 6 us before it began
    assert _flat(pre["readbacks"]) == pytest.approx(
        [150 * US, 205 * US, 206 * US, 210 * US])
    assert ls.latest_before([10, 30], [102, 151]) == [0, 1]
    assert ls.latest_before([10, 30], [35, 36, 37]) == [0, 1, None]
    assert ls.latest_before([10, 30], [5, 12, 31]) == [None, 0, 1]


def test_the_idle_of_the_lead_device_splits_three_ways():
    r = _reduce(host_bound_planes())
    idle = r["idle"]
    # three gaps of 65 us between four ticks: 20 us of each with the call
    # begun; every wait lies under a running tick
    assert idle["idle_s"] == pytest.approx(195e-6)
    assert idle["launch_s"] == pytest.approx(60e-6)
    assert idle["readback_s"] == pytest.approx(0.0)
    assert idle["neither_s"] == pytest.approx(135e-6)
    # where two ticks are 5 us apart nothing counts as idle
    assert _reduce(device_bound_planes())["idle"] is None


@pytest.mark.parametrize("planes", [host_bound_planes, device_bound_planes])
def test_the_three_leaves_add_up_to_the_engines_host_work(planes):
    p = planes()
    spans = ps.reduce_planes(p)
    r = ls.reduce_planes(p, spans["tick_ms"])
    leaves = [r[f"engine_{leaf}_ms_per_tick.serve"] for leaf in ls.LEAVES]
    others = sum(v for n, v in spans["tick_ms"].items()
                 if ps.WAIT not in n and not any(
                     n in names for names in ls.LEAVES.values()))
    assert all(v > 0 for v in leaves) and others > 0
    assert sum(leaves) + others \
        == pytest.approx(spans["engine_host_ms_per_tick"])
    if planes is host_bound_planes:
        assert leaves == pytest.approx([0.010, 0.030, 0.010])


def test_the_prefills_leaves_count_towards_a_tick(reading_of):
    planes = host_bound_planes()
    planes[1]["lines"][0]["events"] += [
        ("mxtpu:serve.prefill.stage", 399 * US, 4 * US),
        ("mxtpu:serve.prefill.dispatch", 403 * US, 8 * US),
        ("mxtpu:serve.prefill.account", 411 * US, 12 * US)]
    reading_of(planes)
    for name, want in zip(ENGINE, (0.011, 0.032, 0.013)):
        assert _read(name, SAW_A_DEVICE) == pytest.approx(want)


def test_a_parent_shaped_trace_gives_the_joined_four_and_no_leaf(reading_of):
    """A program from before the cut, traced with this benchmark: one wide
    ``serve.tick.dispatch`` and no ``serve.tick.stage``. Its dispatch span
    is not read under the narrowed one's name; the join reads spans it has
    always had."""
    wide = [("serve.tick.dispatch", 10, 60) if s[0] == "serve.tick.stage"
            else s for s in HOST_BOUND
            if s[0] not in ("serve.tick.dispatch", "serve.tick.account")]
    r = reading_of(host_bound_planes(template=wide))
    for name in ENGINE:
        assert _read(name, SAW_A_DEVICE) is None
    # the wide span began 10 us earlier: so much more of the gap is exposed
    assert _read("launch_exposed_ms.serve", SAW_A_DEVICE) \
        == pytest.approx(0.030)
    assert _read("readback_ms.serve", SAW_A_DEVICE) == pytest.approx(0.010)
    lines = []
    ls.report(r, say=lines.append)
    assert not any("engine thread" in ln for ln in lines)


@pytest.mark.parametrize("name", NEW)
def test_a_run_that_saw_no_device_reports_nothing(reading_of, name):
    reading_of(host_bound_planes() if name.endswith(".serve")
               else train_planes())
    assert _read(name, SAW_A_DEVICE) is not None
    assert _read(name, {"trace": None}) is None
    assert _read(name, {}) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_reports_nothing(reading_of, name):
    """A commit from before the spans: the readers return None and do not
    raise, and the print says so."""
    planes = host_bound_planes()
    planes[1]["lines"] = [{"name": "python3", "events": [
        ("bench:client_wait", 0.0, 400 * US)]}]
    r = reading_of(planes)
    assert _read(name, SAW_A_DEVICE) is None
    lines = []
    ls.report(r, say=lines.append)
    assert lines == ["launch path: the trace holds no run of a named "
                     "program under a dispatch span"]


def test_the_reading_is_printed_before_the_result_line():
    lines = []
    ls.report(_reduce(host_bound_planes()),
              {"serve.tick.dispatch": {296}, "serve.prefill.dispatch":
               {297, 296}}, lines.append)
    ls.report(_reduce(train_planes()), say=lines.append)
    text = "\n".join(lines)
    assert all(ln.startswith("launch path: ") for ln in lines)
    assert "tick, 4 runs: exposed launch ms median 0.0200 p95 0.0200 " \
        "over 4; read-back ms median 0.0100 p95 0.0100 over 3" in text
    assert "exposed launch 0.0001s (30.8%), read-back 0.0000s (0.0%), " \
        "neither (the host not yet at the call) 0.0001s (69.2%)" in text
    assert "ms a tick: stage 0.010, launch 0.030, account 0.010" in text
    assert "serve.prefill.dispatch 296 297, serve.tick.dispatch 296" in text
    assert "step, 3 runs: exposed launch ms median 0.0060" in text


def test_no_trace_file_is_no_reading(tmp_path):
    assert ls.reading(str(tmp_path)) is None


def test_a_trace_recorded_on_the_cpu_loads(run_cell, tmp_path, capsys):
    """The tiny serving cell under the runner's own profiler session: the
    file loads, the leaves are there with the operand count on the dispatch
    spans, and for want of a device there is nothing to join and every
    reader returns None."""
    obs, found = run_cell("gpt-tiny.decode-tiny", seconds=4.0, trace=True)
    assert obs["trace"] is None
    trace_dir = str(tmp_path / "trace")
    r = ls.reading(trace_dir)
    assert r is not None and r["kinds"] == {} and r["idle"] is None
    assert all(r[n] is None for n in JOINED)
    tick_ms = ps.reading(trace_dir)["tick_ms"]
    for leaf, names in ls.LEAVES.items():
        assert set(names) <= set(tick_ms)
        assert r[f"engine_{leaf}_ms_per_tick.serve"] \
            == pytest.approx(sum(tick_ms[n] for n in names))
    out = capsys.readouterr().out
    assert "launch path: the trace holds no run" in out
    assert "launch path: operands handed to the executable: " \
        "serve.prefill.dispatch " in out
    assert ls.reading(trace_dir) is r                   # loaded once
    from chipbench import trace_reduce
    operands = ls.operands_of(trace_reduce.newest_xplane(trace_dir))
    assert set(operands) == {"serve.tick.dispatch", "serve.prefill.dispatch"}
    assert all(n > 20 for v in operands.values() for n in v)
    ours = {n: reader.read(obs) for n, reader in found["readers"].items()
            if n in NEW}
    assert len(ours) == 5 and set(ours.values()) == {None}


def test_the_manifest_names_the_seven_metrics(manifest):
    """Looked up by name: each has a reader file, a layer the benchmark
    already named, and every cell of its kind but one: the A.X-K1 cell's
    own test pins the exact set of metrics that list it
    (``test_chipbench_axk1.py``), so no later metric can (PERF.md 7)."""
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    accepted = {m["layer"] for m in manifest["per_layer"]
                if m["name"] not in NEW}
    kind = {"train_lm": "train", "serve_decode": "serve"}
    cells = {"train": set(), "serve": set()}
    for cell in manifest["workloads"]:
        runner = harness.load_json(BENCH, "traffic",
                                   cell["traffic"])["runner"]
        cells[kind[runner]].add(cell["name"])
    assert len(cells["train"]) == len(cells["serve"]) == 3
    cells["serve"].remove("A.X-K1.decode-saturated-64")
    layer = {"engine": "admission and batching (serve/decode/engine.py)",
             "serve": "compiled programs, serving (serve/decode/programs.py)",
             "train": "compiled programs, training (train_step.py)"}
    for name in NEW:
        m = by_name[name]
        side = name.rsplit(".", 1)[1]
        assert (m["source"], m["unit"], m["better"]) \
            == ("program_span", "ms", "lower")
        assert m["moves"] == f"{side}_tokens_per_s"
        assert m["layer"] in accepted
        assert m["layer"] == layer["engine" if name in ENGINE else side]
        assert set(m["workloads"]) == cells[side]
        assert callable(harness.load_module(BENCH, "layer_metrics",
                                            name).read)
