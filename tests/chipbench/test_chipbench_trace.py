"""The trace reduction on a hand-built trace whose answers are known."""
import pytest

import chipbench_paths  # noqa: F401 — puts the benchmark on sys.path
from chipbench import trace_reduce as tr

US = 1000.0   # the trace's times are nanoseconds

# operations as a TPU trace names them: the whole HLO instruction
FUSION_1 = ("%fusion.1 = (f32[4]{0:T(1024)S(1)}, f32[4,8]{1,0:T(4,128)}) "
            "fusion(f32[4,8]{1,0:T(8,128)S(1)} %copy-done.2), kind=kLoop")
KERNEL = ("%custom-call.2 = f32[4,16,8]{2,1,0:T(8,128)} custom-call("
          "f32[4,16,8]{2,1,0:T(8,128)} %fusion.1), "
          'custom_call_target="tpu_custom_call"')
READS_KERNEL = ("%fusion.3 = f32[4,16,8]{2,1,0:T(8,128)} fusion("
                "f32[4,16,8]{2,1,0:T(8,128)} %custom-call.2), kind=kLoop")


def _trace():
    """Two runs of a step program and one of a small program on one device.

    step run 1: 0-100 us fusion.1, 100-150 us custom-call.2, (hole 150-160),
    160-200 us fusion.3; idle 200-400 us while the host waits on the loss;
    step run 2: 400-500 fusion.1, 500-550 custom-call.2, 560-600 fusion.3;
    idle 600-700 us with no span of the benchmark; small program 700-720.
    """
    ops, modules = [], []
    for base in (0, 400):
        ops += [(FUSION_1, (base + 0) * US, 100 * US),
                (KERNEL, (base + 100) * US, 50 * US),
                (READS_KERNEL, (base + 160) * US, 40 * US)]
        # the runs of one program share its fingerprint
        modules.append(("jit_step(7777)", base * US, 200 * US))
    ops.append(("%copy.9 = f32[8]{0} copy(f32[8]{0} %p)", 700 * US, 20 * US))
    modules.append(("jit_step(9999)", 700 * US, 20 * US))
    host = [("bench:step_call", -20 * US, 30 * US),
            ("bench:loss_wait", 190 * US, 215 * US),
            ("bench:step_call", 395 * US, 10 * US),
            ("not the benchmark's", 600 * US, 100 * US)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules},
            {"name": "Steps", "events": [("0", 0.0, 720 * US)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
        {"name": "/host:metadata", "lines": []},
    ]


@pytest.fixture(scope="module")
def summary():
    return tr.reduce_trace(_trace())


def test_union_merges_and_measures():
    u = tr.Union([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert u.spans == [[0, 20], [30, 45]]
    assert u.total == 35
    assert u.covered(15, 35) == 10
    assert u.covered(-5, 100) == 35
    assert u.covered(20, 30) == 0
    assert u.gaps(5) == [(20, 30)] and u.gaps(11) == []


@pytest.mark.parametrize("key,expected", [
    ("window_s", 720e-6),
    ("busy_s", (190 + 190 + 20) * 1e-6),
    ("idle_share", 1 - 400 / 720),
    ("custom_call_s", 100e-6),
    ("devices", 1),
])
def test_busy_union_and_idle_share(summary, key, expected):
    assert summary[key] == pytest.approx(expected)


def test_time_per_program(summary):
    progs = summary["programs"]
    # two programs of one Python function differ in their fingerprints
    assert set(progs) == {"jit_step(7777)", "jit_step(9999)"}
    step = progs["jit_step(7777)"]
    # busy time INSIDE a run: the 10 us hole between two ops is not busy
    assert step["count"] == 2
    assert step["median_s"] == pytest.approx(190e-6)
    assert step["total_s"] == pytest.approx(380e-6)
    assert progs["jit_step(9999)"]["count"] == 1


def test_top_operations(summary):
    ops = dict(summary["device_ops"])
    assert summary["device_ops"][0][0] == tr.short_name(FUSION_1)
    assert ops[tr.short_name(FUSION_1)] == pytest.approx(200e-6)
    assert ops[tr.short_name(KERNEL)] == pytest.approx(100e-6)
    assert len(summary["device_ops"]) <= 10
    assert all(len(name) <= 120 for name in ops)


def test_gaps_go_to_the_host_span_that_covers_them(summary):
    gaps = dict(summary["idle_gaps"])
    # 200-400 us lies under loss_wait (190-405); the step_call span that
    # starts at 395 covers only 5 us of it. 600-700 us has no span of the
    # benchmark. The 10 us holes are under the threshold.
    assert gaps == {"loss_wait": pytest.approx(200e-6),
                    "unattributed": pytest.approx(100e-6)}


def test_unattributed_takes_the_runners_name():
    s = tr.reduce_trace(_trace(), unattributed="unattributed (engine thread)")
    assert "unattributed (engine thread)" in dict(s["idle_gaps"])


def test_no_device_plane_gives_nothing():
    host_only = [p for p in _trace() if not p["name"].startswith("/device")]
    assert tr.reduce_trace(host_only) is None


def test_modules_alone_still_give_busy_time():
    planes = _trace()
    planes[0]["lines"] = [ln for ln in planes[0]["lines"]
                          if ln["name"] != "XLA Ops"]
    s = tr.reduce_trace(planes)
    assert s["busy_s"] == pytest.approx(420e-6)
    assert s["programs"]["jit_step(7777)"]["median_s"] == \
        pytest.approx(200e-6)
    assert s["custom_call_s"] == 0


@pytest.mark.parametrize("name,opcode", [
    (FUSION_1, "fusion"), (KERNEL, "custom-call"),
    (READS_KERNEL, "fusion"),
    ("%copy.229 = f32[128,24]{0,1:T(8,128)} copy(f32[128,24]{1,0} %a)",
     "copy"),
    ("custom-call.12", "custom-call"), ("fusion.3", "fusion"),
    ("%while = (s32[], f32[2]{0}) while((s32[], f32[2]{0}) %tuple)",
     "while"),
])
def test_opcode_of_an_operation(name, opcode):
    assert tr.hlo_opcode(name) == opcode
    assert tr.is_custom_call(name) is (opcode == "custom-call")


def test_two_devices_are_averaged():
    planes = _trace()
    second = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [("fusion.1", 0.0, 360 * US)]}]}
    s = tr.reduce_trace(planes + [second])
    assert s["devices"] == 2
    assert s["busy_s"] == pytest.approx((400 + 360) / 2 * 1e-6)
    assert s["window_s"] == pytest.approx((720 + 360) / 2 * 1e-6)


def test_a_recorded_trace_goes_through_the_same_reduction(tmp_path):
    """A trace recorded here on the CPU has no device plane; loading it must
    keep only the benchmark's spans of the host plane."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tr.start(str(tmp_path))
    with TraceAnnotation("bench:step_call"):
        f(x).block_until_ready()
    with TraceAnnotation("someone else's"):
        f(x).block_until_ready()
    tr.stop()
    assert tr.reduce_dir(str(tmp_path)) is None
    planes = tr.load_xplane(tr.newest_xplane(str(tmp_path)))
    spans = tr.host_spans(planes)
    assert [name for _, _, name in spans] == ["step_call"]
    assert "PLANE /host:CPU" in tr.inventory(tr.newest_xplane(str(tmp_path)))
