"""Fixtures of the chip benchmark's tests: the benchmark's own directory on
``sys.path``, and a copy of it extended by the fixture directory's NEW files
(tiny cells for the CPU, a toy runner and metric) — added without an edit to
any file that is there, which is what a later PR has to be able to do."""
import copy
import json
import os
import shutil
import time

import pytest

from chipbench_paths import BENCH, FIXTURE, ROOT


@pytest.fixture(scope="session")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="session")
def extended(manifest, tmp_path_factory):
    """(bench_dir, manifest): the benchmark plus the fixture's files and
    entries. Copying refuses to overwrite: every fixture file is new."""
    bench = str(tmp_path_factory.mktemp("bench") / "chip")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", ".work", "__pycache__"))
    for kind in ("configs", "traffic", "runners", "layer_metrics"):
        for name in os.listdir(os.path.join(FIXTURE, kind)):
            target = os.path.join(bench, kind, name)
            assert not os.path.exists(target), f"{target} is not new"
            shutil.copy(os.path.join(FIXTURE, kind, name), target)
    with open(os.path.join(FIXTURE, "manifest_extra.json")) as fh:
        extra = json.load(fh)
    merged = copy.deepcopy(manifest)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        merged[group] = merged[group] + extra[group]
    for pattern, cells in extra["extend_workloads"].items():
        for m in merged["end_to_end"] + merged["per_layer"]:
            hit = m["name"] == pattern or (
                pattern.startswith("*") and m["name"].endswith(pattern[1:]))
            if hit and "workloads" in m:
                m["workloads"] = m["workloads"] + cells
    return bench, merged


@pytest.fixture
def run_cell(extended, tmp_path):
    """Run one cell's runner on the CPU, as ``harness.main`` does past its
    TPU check; returns (observations, resolved files)."""
    from chipbench import harness

    bench, merged = extended

    def run(workload, seconds=1.5, trace=False, seed=2**31 + 11):
        found = harness.resolve(merged, workload, bench)
        env = harness.Env(bench, str(tmp_path), found["cell"]["chips"],
                          time.perf_counter(), harness.CompileLog())
        obs = found["runner"].run(found["config"], found["traffic"], seed,
                                  seconds, trace, env)
        return obs, found

    return run
