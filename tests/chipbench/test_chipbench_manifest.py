"""BENCHMARK.json is consistent with the files it names and with the
contract's limits that a test can check without the driver."""
import json
import os
import re

import pytest

from chipbench_paths import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert manifest["command"][:2] == ["python3", "benchmark/chip/run.py"]
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_run_seconds_fits_the_checks_budget(manifest):
    s = manifest["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def _entries(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            yield group, e


def test_names_units_and_lines(manifest):
    for group, e in _entries(manifest):
        assert NAME.match(e["name"]), (group, e["name"])
        for key in ("why", "layer", "source"):
            if key in e and (group, key) != ("end_to_end", "source") \
                    and (group, key) != ("per_layer", "source"):
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key], (e["name"], key)
        if "unit" in e:
            assert UNIT.match(e["unit"]), e
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names)), group
    metric_names = [m["name"] for m in manifest["end_to_end"]
                    + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_entries_have_just_the_contracts_keys(manifest):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }
    for group, e in _entries(manifest):
        assert set(e) <= allowed[group], (group, e["name"])
        assert allowed[group] - {"workloads"} <= set(e), (group, e["name"])


def test_end_to_end_sources_and_bounds(manifest):
    names = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in names
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def test_every_cells_files_exist(manifest):
    from chipbench import harness

    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    pairs = set()
    for cell in manifest["workloads"]:
        assert cell["chips"] in (1, 4)
        assert NAME.match(cell["traffic"]) and NAME.match(cell["config"])
        assert (cell["config"], cell["traffic"]) not in pairs
        pairs.add((cell["config"], cell["traffic"]))
        used.add(cell["config"])
        found = harness.resolve(manifest, cell["name"], BENCH)
        assert callable(found["runner"].run)
        assert all(callable(r.read) for r in found["readers"].values())
        assert found["config"]["name"] == cell["config"]
        task = found["config"]["task"]
        assert os.path.isfile(os.path.join(BENCH, "tasks", task + ".py"))
    assert used == set(configs), "a configuration no cell uses"
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["file"] == f"benchmark/chip/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as fh:
            body = json.load(fh)
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
    four = sum(c["chips"] == 4 for c in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_every_cell_reports_enough(manifest):
    from chipbench import harness

    for cell in manifest["workloads"]:
        e2e = {m["name"] for m in harness.metrics_of(
            manifest, "end_to_end", cell["name"])}
        layer = harness.metrics_of(manifest, "per_layer", cell["name"])
        assert "setup_s" in e2e and len(e2e) >= 2 and layer, cell["name"]
        for m in layer:
            assert m["moves"] in e2e, (cell["name"], m["name"], m["moves"])


def test_metric_workload_lists_name_real_cells(manifest):
    cells = {c["name"] for c in manifest["workloads"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        assert m.get("workloads", True), m["name"]


def test_layers_of_one_module_are_spelt_alike(manifest):
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert len({name.lower() for name in layers}) == len(layers)
    with open(os.path.join(ROOT, "PERF.md")) as fh:
        perf = fh.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_files_under_paths_use_allowed_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in manifest["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d not in (
                ".cache", ".work", "__pycache__", ".pytest_cache")]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel


def test_peaks_table_refuses_an_unknown_device():
    from chipbench import peaks

    row = peaks.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9 and row["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v5")
