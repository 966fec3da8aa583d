"""Each runner at a tiny size on the CPU, the result line, and the ways a
later PR extends the benchmark without an edit."""
import json
import os
import subprocess
import sys

import pytest

from chipbench_paths import BENCH, ROOT
from chipbench import harness

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _line(merged, workload, obs, found, trace):
    obs = dict(obs, memory_peak_bytes=obs.get("memory_peak_bytes") or 1)
    return harness.build_result(merged, workload, obs, found["readers"],
                                trace, DEVICE)


@pytest.mark.parametrize("workload", ["gpt-tiny.train-tiny",
                                      "bert-tiny.pretrain-tiny",
                                      "gpt-tiny-dp2tp2.train-tiny"])
def test_train_runner_at_a_tiny_size(extended, run_cell, workload):
    obs, found = run_cell(workload)
    assert obs["correct"] is True, obs["checks"]
    assert obs["attempted"] > 5 and obs["failed"] == 0
    assert obs["counters"]["steps"] == obs["attempted"]
    assert len(obs["spans"]["host_dispatch_s"]) == obs["attempted"]
    result = _line(extended[1], workload, obs, found, trace=False)
    assert set(result) == RESULT_KEYS
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    json.dumps(result)


def test_serve_runner_at_a_tiny_size(extended, run_cell):
    workload = "gpt-tiny.decode-tiny"
    obs, found = run_cell(workload, seconds=2.0)
    assert obs["correct"] is True, obs["checks"]
    assert obs["attempted"] > 10 and obs["failed"] == 0
    c = obs["counters"]
    assert c["ticks"] > 0 and c["prefills"] > 0
    assert 0.5 < c["slot_occupancy"] <= 1.0
    result = _line(extended[1], workload, obs, found, trace=False)
    assert set(result) == RESULT_KEYS
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_ms_p95",
                                      "setup_s"}
    assert obs["end_to_end"]["ttft_ms_p95"] > 0   # printed, not bounded
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_traced_line_leaves_out_what_no_trace_gave(extended, run_cell):
    """On the CPU the profiler sees no device: the readers of the trace
    return nothing and are left out; a traced line with no device time is
    refused, as the driver would refuse it."""
    workload = "gpt-tiny.train-tiny"
    obs, found = run_cell(workload, trace=True)
    assert obs["trace"] is None
    values = {name: r.read(obs) for name, r in found["readers"].items()
              if name != "model_flops_util.train"}
    assert values["step_device_ms.train"] is None
    assert values["device_idle_share.train"] is None
    assert values["pallas_time_share.train"] is None
    assert values["host_dispatch_ms.train"] > 0
    assert values["first_call_s.train"] > 0
    with pytest.raises(harness.BenchError):
        _line(extended[1], workload, obs, found, trace=True)
    with pytest.raises(Exception):   # the CPU is not in the peaks table
        found["readers"]["model_flops_util.train"].read(obs)


def test_per_layer_readers_on_known_observations(extended):
    bench, merged = extended
    obs = {
        "spans": {"host_dispatch_s": [0.002, 0.004, 0.003],
                  "first_call_s": 17.5, "warmup_s": 11.0},
        "counters": {"n_params": 354.8e6, "tokens_per_s": 20000.0,
                     "device_kind": "TPU v5 lite",
                     "peak_bytes_in_use": 12 * 2**30, "slot_occupancy": 0.9,
                     "kv_pages": 128, "kv_pages_live": [32, 64]},
        "trace": {"busy_s": 2.0, "window_s": 2.5, "idle_share": 0.2,
                  "custom_call_s": 0.5,
                  "programs": {
                      "jit_observed(1)": {"count": 60, "total_s": 1.2,
                                          "median_s": 0.02},
                      "jit_observed(2)": {"count": 5, "total_s": 0.5,
                                          "median_s": 0.1},
                      "jit_observed(3)": {"count": 3, "total_s": 0.299,
                                          "median_s": 0.1},
                      # run with every dispatch, and far too small to be
                      # the tick
                      "jit__threefry_split(4)": {"count": 68,
                                                 "total_s": 0.001,
                                                 "median_s": 0.00001}}},
    }
    expected = {
        "host_dispatch_ms.train": 3.0, "first_call_s.train": 17.5,
        "warmup_s.serve": 11.0, "peak_hbm_gib.train": 12.0,
        "peak_hbm_gib.serve": 12.0, "slot_occupancy.serve": 90.0,
        "kv_pages_live_share.serve": 37.5,
        "device_idle_share.train": 20.0, "device_idle_share.serve": 20.0,
        "pallas_time_share.train": 25.0,
        "model_flops_util.train": 100 * 6 * 354.8e6 * 20000 / 197e12,
        "tick_device_ms.serve": 20.0, "prefill_share.serve": 40.0,
        # the training step is the program with most device time
        "step_device_ms.train": 20.0,
    }
    names = {m["name"] for m in merged["per_layer"]} - {"toy_done",
                                                        "toy_absent"}
    assert names == set(expected)
    for name, want in expected.items():
        got = harness.load_module(bench, "layer_metrics", name).read(obs)
        assert got == pytest.approx(want), name


def test_a_cell_runner_and_metric_from_new_files_only(extended):
    """The fixture directory adds a configuration, a traffic mix, a runner
    and two per-layer metrics as new files and new entries; nothing that
    was there is edited, and the harness finds them by name."""
    bench, merged = extended
    found = harness.resolve(merged, "toy.toy-mix", bench)
    env = harness.Env(bench, bench, 1, 0.0, None)
    for trace in (False, True):
        obs = found["runner"].run(found["config"], found["traffic"], 1, 3.0,
                                  trace, env)
        result = harness.build_result(merged, "toy.toy-mix", obs,
                                      found["readers"], trace, DEVICE)
        assert set(result) - {"breakdown"} == RESULT_KEYS
        assert result["attempted"] == 21
        assert result["device"]["memory_peak_bytes"] == 1024
        if trace:
            # toy_absent found nothing to read and is left out
            assert result["metrics"] == {
                "toy_done": {"value": 21.0, "unit": "ops"}}
            assert result["device"]["busy_s"] == 0.5
            assert result["breakdown"]["idle_gaps"] == [["wait", 0.5]]
        else:
            assert result["metrics"] == {
                "toy_ops_per_s": {"value": 7.0, "unit": "ops/s"},
                "setup_s": {"value": 0.25, "unit": "s"}}


def test_an_unknown_cell_or_a_missing_file_is_an_error(extended, manifest):
    bench, merged = extended
    with pytest.raises(harness.BenchError):
        harness.resolve(manifest, "no-such.cell", BENCH)
    with pytest.raises(harness.BenchError):
        # the real benchmark's directory does not hold the fixture's files
        harness.resolve(merged, "toy.toy-mix", BENCH)


def test_a_runner_that_gives_no_end_to_end_metric_is_an_error(extended):
    bench, merged = extended
    found = harness.resolve(merged, "toy.toy-mix", bench)
    obs = found["runner"].run(found["config"], found["traffic"], 1, 3.0,
                              False, None)
    del obs["end_to_end"]["toy_ops_per_s"]
    with pytest.raises(harness.BenchError):
        harness.build_result(merged, "toy.toy-mix", obs, found["readers"],
                             False, DEVICE)


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "chip", "run.py"),
         "--workload", "gpt2-medium.train-1k", "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_without_a_tpu_prints_no_result_line():
    proc = _run_py(ROOT)
    assert proc.returncode not in (0, None)
    assert "no TPU" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_run_py_without_the_program_prints_no_result_line(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's paths
    has no system under test."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark" / "chip",
                    ignore=shutil.ignore_patterns(".cache", ".work",
                                                  "__pycache__"))
    proc = _run_py(str(tmp_path))
    assert proc.returncode not in (0, None)
    assert "not here" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())
