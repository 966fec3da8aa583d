"""A later PR adds a cell by appending files and names, and edits nothing that
is there. So every test of this directory that takes the manifest alone runs
here twice: on ``BENCHMARK.json``, and on a copy with
``fixture/appended/manifest.json`` appended at the end: a serving
configuration and cell listed in ``serve_tokens_per_s``, ``itl_ms_p95`` and
every ``*.serve`` metric (those that know no model and those that count by a
task's cost module alike: the most a later cell can list), a ``*.serve``
metric that lists only that cell, and a four-chip training configuration and
cell listed in ``train_tokens_per_s`` and every ``*.train`` metric. A test
that pins a position, a count or the exact set of the whole manifest fails
the second case."""
import copy
import glob
import importlib
import inspect
import json
import os
import shutil

import pytest

from chipbench_paths import BENCH, FIXTURE, HERE

APPENDED = os.path.join(FIXTURE, "appended")
# takes the manifest, but finds every cell's files at their paths in the
# repo: the appended cells' files are checked by
# ``test_the_appended_cells_resolve_from_new_files``
ON_DISK = {"test_every_cells_files_exist"}


def _manifest_checks():
    """Every ``test_*`` of this directory whose one argument is
    ``manifest``, found by its signature: a cell test added later is
    checked here without an edit."""
    for path in sorted(glob.glob(os.path.join(HERE, "test_chipbench_*.py"))):
        name = os.path.basename(path)[:-3]
        if name == __name__:
            continue
        module = importlib.import_module(name)
        for attr, fn in sorted(vars(module).items()):
            if attr.startswith("test_") and attr not in ON_DISK \
                    and inspect.isfunction(fn) \
                    and list(inspect.signature(fn).parameters) \
                    == ["manifest"]:
                short = name[len("test_chipbench_"):]
                yield pytest.param(fn, id=f"{short}.{attr}")


def _extra():
    with open(os.path.join(APPENDED, "manifest.json")) as fh:
        return json.load(fh)


def _appended(manifest):
    """The manifest with the fixture's cells added to the lists it names
    (``*.serve``: every metric whose name ends so), then its entries
    appended at the end of each group."""
    extra = _extra()
    merged = copy.deepcopy(manifest)
    for pattern, cells in extra["extend_workloads"].items():
        for m in merged["end_to_end"] + merged["per_layer"]:
            hit = m["name"] == pattern or (
                pattern.startswith("*") and m["name"].endswith(pattern[1:]))
            if hit and "workloads" in m:
                m["workloads"] = m["workloads"] + cells
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        merged[group] = merged[group] + extra.get(group, [])
    return merged


CHECKS = list(_manifest_checks())


def test_the_checks_are_found():
    files = {p.id.split(".", 1)[0] for p in CHECKS
             if ".test_the_real_cell_is_in_the_manifest" in p.id}
    assert {"axk1", "granite_hybrid", "qwen3_next"} <= files
    ids = {p.id for p in CHECKS}
    assert {"launch_spans.test_the_manifest_names_the_seven_metrics",
            "program_spans.test_the_manifest_names_the_new_metrics",
            "manifest.test_every_cell_reports_enough"} <= ids


@pytest.mark.parametrize("which", ["real", "appended"])
@pytest.mark.parametrize("check", CHECKS)
def test_the_manifest_checks_hold_with_cells_appended(manifest, check,
                                                      which):
    check(manifest if which == "real" else _appended(manifest))


def test_the_appended_cells_resolve_from_new_files(manifest, tmp_path):
    """The fixture's cells are whole: a copy of the benchmark with the
    fixture's files added (none replaces one) resolves each of them, and
    the appended manifest keeps the contract's share of four-chip cells."""
    from chipbench import harness

    bench = str(tmp_path / "chip")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", ".work", "__pycache__"))
    for kind in ("configs", "traffic", "layer_metrics"):
        for name in os.listdir(os.path.join(APPENDED, kind)):
            target = os.path.join(bench, kind, name)
            assert not os.path.exists(target), f"{target} is not new"
            shutil.copy(os.path.join(APPENDED, kind, name), target)
    merged = _appended(manifest)
    for cell in _extra()["workloads"]:
        found = harness.resolve(merged, cell["name"], bench)
        assert found["config"]["name"] == cell["config"]
        assert os.path.isfile(os.path.join(
            bench, "tasks", found["config"]["task"] + ".py"))
        assert found["traffic"]["runner"] in ("serve_decode", "train_lm")
        assert all(callable(r.read) for r in found["readers"].values())
    four = sum(c["chips"] == 4 for c in merged["workloads"])
    assert four <= max(1, len(merged["workloads"]) // 4)
