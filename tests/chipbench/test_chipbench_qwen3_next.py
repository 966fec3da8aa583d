"""The Qwen3-Next cell at a tiny size on the CPU: ``train_lm`` runs it
unchanged through the new task, and every reader the cell brings returns a
number on its observations. The tiny configuration and traffic are written
here, as NEW files of a copy of the benchmark: nothing that is there is
edited, ``fixture/manifest_extra.json`` included."""
import copy
import json
import os
import shutil
import time

import pytest

from chipbench_paths import BENCH

CELL = "qwen3-next-tiny.train-4k-tiny"
REAL = "qwen3-next-80b-a3b.train-4k"
NEW_METRICS = ("mfu_active.train", "expert_load_max_over_mean.train",
               "gdn_time_share.train", "experts_time_share.train",
               "attn_time_share.train")


def _tiny_config():
    """The real file with tiny sizes: every key the task reads stays."""
    with open(os.path.join(BENCH, "configs", "qwen3-next-80b-a3b.json")) as fh:
        config = json.load(fh)
    config.update(
        name="qwen3-next-tiny", hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        num_experts_per_tok=4, router_num_experts=16, experts_held=[4, 12],
        num_experts=8, vocab_size=96, logits_tolerance=0.001,
        logits_rms_tolerance=0.001)
    return config


TRAFFIC = {
    "runner": "train_lm", "rows": 2, "length": 40,
    "tokens": {"zipf_exponent": 1.0, "perm_seed": 0},
    "optimizer": "adam", "optimizer_params": {"learning_rate": 0.003},
    "ring": 4, "check_rows": 2, "warm_steps": 1, "log_every": 2,
    "trace_seconds": 0.5,
}


@pytest.fixture(scope="module")
def bench(manifest, tmp_path_factory):
    """(bench_dir, manifest) with the tiny cell beside the real one."""
    bench_dir = str(tmp_path_factory.mktemp("qwen_bench") / "chip")
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns(
        ".cache", ".work", "__pycache__"))
    for kind, name, body in (("configs", "qwen3-next-tiny", _tiny_config()),
                             ("traffic", "train-4k-tiny", TRAFFIC)):
        path = os.path.join(bench_dir, kind, name + ".json")
        assert not os.path.exists(path)
        with open(path, "w") as fh:
            json.dump(body, fh)
    merged = copy.deepcopy(manifest)
    merged["configs"].append({"name": "qwen3-next-tiny",
                              "source": "tests only", "reduced": [],
                              "file": "configs/qwen3-next-tiny.json",
                              "why": "CPU tests"})
    merged["workloads"].append({"name": CELL, "config": "qwen3-next-tiny",
                                "traffic": "train-4k-tiny", "chips": 1,
                                "why": "train_lm with the Qwen3-Next task"})
    for m in merged["end_to_end"] + merged["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [CELL]
    return bench_dir, merged


@pytest.fixture(scope="module")
def run(bench, tmp_path_factory):
    """One run of the tiny cell: (observations, resolved files)."""
    from chipbench import harness

    bench_dir, merged = bench
    found = harness.resolve(merged, CELL, bench_dir)
    env = harness.Env(bench_dir, str(tmp_path_factory.mktemp("work")), 1,
                      time.perf_counter(), harness.CompileLog())
    obs = found["runner"].run(found["config"], found["traffic"], 2**31 + 28,
                              2.0, False, env)
    return obs, found


def test_the_real_cell_is_in_the_manifest_as_the_issue_gives_it(manifest):
    from chipbench import harness

    cell = harness.find_cell(manifest, REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b", "train-4k", 1)
    found = harness.resolve(manifest, REAL, BENCH)
    mix = found["traffic"]
    assert (mix["runner"], mix["rows"], mix["length"]) == ("train_lm", 1,
                                                           4096)
    assert (mix["ring"], mix["check_rows"], mix["warm_steps"],
            mix["log_every"], mix["trace_seconds"]) == (8, 1, 3, 10, 3.0)
    assert mix["optimizer"] == "adam" and \
        mix["optimizer_params"] == {"learning_rate": 0.0003}
    listed = {m["name"] for m in manifest["per_layer"]
              if REAL in m.get("workloads", ())}
    assert set(NEW_METRICS) <= listed
    # 6 x every parameter held would count 32 experts a token where a token
    # multiplies 0.625 of one: that metric does not list the cell
    assert "model_flops_util.train" not in listed


def test_the_configuration_keeps_every_published_width():
    import sys

    zoo = sys.modules["mxnet_tpu.gluon.model_zoo.qwen3_next"]
    with open(os.path.join(BENCH, "configs", "qwen3-next-80b-a3b.json")) as fh:
        config = json.load(fh)
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in zoo.QWEN3_NEXT_80B_A3B.items():
        if key in reduced:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["router_num_experts"] == 512
    assert config["experts_held"] == [0, config["num_experts"]] == [0, 32]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["deployment"] and config["assumed"]
    # the count the runner prints, from the shapes: within 1% of 625.7M
    from chipbench import qwen3_next_cost as cost

    tasks = os.path.join(BENCH, "tasks")
    sys.path.insert(0, tasks)
    try:
        import qwen3_next_lm
    finally:
        sys.path.remove(tasks)
    cfg = qwen3_next_lm.model_config(config)
    small = (2 * 8192 * 4 + 2 * 32 + 128) * 3 + 2 * 256 + 5 * 2048 * 4 \
        + 512 * 4 + 2048
    n = (cost.matrix_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
         + 4 * 32 * cost.expert_params(cfg) + small)
    assert abs(n - 625.7e6) < 0.01 * 625.7e6, n


def test_train_lm_runs_the_tiny_cell(bench, run):
    from chipbench import harness

    obs, found = run
    assert obs["correct"] is True, obs["checks"]
    assert obs["attempted"] > 3 and obs["failed"] == 0
    result = harness.build_result(
        bench[1], CELL, dict(obs, memory_peak_bytes=1), found["readers"],
        False, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    # 4 layers x 80 tokens x top-4 pairs, half the experts held
    from mxnet_tpu import telemetry

    report = telemetry.moe_report()
    assert report["moe.pairs_total"] >= 4 * 80 * 4
    # one chip alone has a sixteenth of the router's gradient: the cell
    # leaves the router as it is, always (the configuration says why)
    from chipbench import qwen3_next_cost

    net = qwen3_next_cost.last_run()["net"]
    assert "router_updated_here" not in found["config"]    # no switch
    assert all(layer.moe.router.weight.grad_req == "null"
               for layer in net.layers)
    assert net.layers[0].moe.gate_up.grad_req == "write"
    assert 0 < report["moe.pairs_here"] < report["moe.pairs_total"]


def test_every_new_reader_returns_a_number(run, monkeypatch):
    """The counters come from the program; the device trace a CPU run has
    none of is stood in for by 1 microsecond an instruction of the REAL
    compiled text, so the join of trace and text is what is tested."""
    from chipbench import scope_time

    obs, found = run
    texts = scope_time.compiled_texts()
    assert texts, "the program gives no compiled text"
    by = {inst: 1e-6 for text in texts
          for inst in scope_time.instruction_scopes(text)}
    monkeypatch.setattr(scope_time, "seconds_by_instruction",
                        lambda trace_dir=None: by)
    obs = dict(obs, trace={"busy_s": 1e-6 * len(by), "window_s": 1.0,
                           "idle_share": 0.5, "custom_call_s": 0.0,
                           "programs": {}},
               counters=dict(obs["counters"], device_kind="TPU v5 lite"))
    values = {name: found["readers"][name].read(obs) for name in NEW_METRICS}
    assert all(isinstance(v, float) and v > 0 for v in values.values()), \
        values
    shares = [values[n] for n in NEW_METRICS if n.endswith("_time_share"
                                                           ".train")]
    assert sum(shares) < 100.0
    assert values["gdn_time_share.train"] > values["attn_time_share.train"]
    assert values["expert_load_max_over_mean.train"] >= 1.0
    assert values["mfu_active.train"] < 100.0


def test_the_scope_readers_return_nothing_without_scopes(run):
    """A program without the scopes, as the parent commit: None, no raise."""
    from chipbench import scope_time

    assert scope_time.scope_seconds(("gdn",), texts=[
        'HloModule m\n  %add.1 = f32[] add(%a, %b), metadata={op_name='
        '"jit(f)/add"}']) is None
    obs, found = run
    assert found["readers"]["gdn_time_share.train"].read(
        dict(obs, trace=None)) is None


NESTED_TEXT = """HloModule jit_mxtpu_train_step
%body.in (p: f32[8]) -> f32[8] {
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/jvp(experts)/while/body/while/body/mul"}
}
%body.out (p: f32[8]) -> f32[8] {
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/jvp(experts)/while/body/add"}
  %while.2 = f32[8]{0} while(%p), body=%body.in, metadata={op_name="jit(s)/jvp(experts)/while/body/while"}
}
ENTRY %main (a: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(s)/jvp(gdn)/mul"}
  %while.1 = f32[8]{0} while(%a), body=%body.out, metadata={op_name="jit(s)/jvp(experts)/while"}
  %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(s)/optimizer/add"}
  ROOT %copy.1 = f32[8]{0} copy(%a)
}
"""


def nested_plane():
    """One device's ops line as the chip writes it: a ``while`` event spans
    its iterations, the body's operations lie inside it, and the body holds
    a loop of its own. Times in ns; 1000 ns busy in all, with a hole."""
    ev = [("%fusion.1 = f32[8]{0} fusion(%a)", 0.0, 100.0),
          ("%while.1 = f32[8]{0} while(%a)", 100.0, 600.0),
          ("%fusion.9 = f32[8]{0} fusion(%a)", 800.0, 250.0),
          ("%copy.1 = f32[8]{0} copy(%a)", 1050.0, 50.0)]
    for it in (0, 1):               # two iterations of 300 ns
        t = 100.0 + 300.0 * it
        ev.append(("%fusion.2 = f32[8]{0} fusion(%p)", t + 10.0, 80.0))
        ev.append(("%while.2 = f32[8]{0} while(%p)", t + 100.0, 190.0))
        ev.append(("%fusion.3 = f32[8]{0} fusion(%p)", t + 110.0, 60.0))
        ev.append(("%fusion.3 = f32[8]{0} fusion(%p)", t + 180.0, 100.0))
    return {"name": "/device:TPU:0",
            "lines": [{"name": "XLA Ops", "events": ev}]}


def test_a_loop_is_counted_once_not_once_a_level(monkeypatch, tmp_path):
    """The ops line nests: the plain sum of durations gives the outer
    ``while`` its 600 ns, the inner ones their 380 and the bodies' fusions
    their 480 again, 1460 ns for a loop that held the device for 600. Every
    moment goes to the innermost event, so the scopes and the rest add up
    to the busy time and no share can pass 100%."""
    from chipbench import scope_time, trace_reduce

    plane = nested_plane()
    events = plane["lines"][0]["events"]
    by = scope_time.self_seconds(events)
    busy = trace_reduce.reduce_device(plane)["busy_s"]
    assert busy == pytest.approx(1000e-9)
    assert sum(by.values()) == pytest.approx(busy)
    assert by["while.1"] == pytest.approx(60e-9)      # 600 - 2 x (80 + 190)
    assert by["while.2"] == pytest.approx(60e-9)      # 2 x (190 - 160)
    assert by["fusion.3"] == pytest.approx(320e-9)
    assert sum(d for _, _, d in events) == pytest.approx(1860.0)

    monkeypatch.setattr(trace_reduce, "newest_xplane",
                        lambda trace_dir: str(tmp_path))
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda path: [
        plane, {"name": "/host:CPU", "lines": []}])
    monkeypatch.setattr(scope_time, "_READINGS", {})
    seconds = {s: scope_time.scope_seconds((s,), texts=[NESTED_TEXT])
               for s in ("gdn", "experts", "optimizer")}
    assert seconds["gdn"] == pytest.approx(100e-9)
    assert seconds["experts"] == pytest.approx(600e-9)
    assert seconds["optimizer"] == pytest.approx(250e-9)
    rest = busy - sum(seconds.values())                # the copy: no scope
    assert rest == pytest.approx(50e-9)
    obs = {"trace": {"busy_s": busy}}
    monkeypatch.setattr(scope_time, "compiled_texts", lambda: [NESTED_TEXT])
    shares = [scope_time.share(obs, (s,))
              for s in ("gdn", "experts", "optimizer")]
    assert shares == pytest.approx([10.0, 60.0, 25.0])


def test_self_seconds_of_events_that_only_overlap():
    """Two events that overlap without nesting (not seen on the chip, but a
    trace is not ours to trust): the union, once."""
    from chipbench import scope_time

    by = scope_time.self_seconds([("%a.1 = x", 0.0, 100.0),
                                  ("%b.1 = x", 50.0, 100.0)])
    assert sum(by.values()) == pytest.approx(150e-9)


def test_scope_pattern_takes_whole_components_only():
    from chipbench import scope_time

    p = scope_time.scope_pattern("gdn")
    assert p.search("jit(step)/grad/transpose(jvp(gdn))/jit(observed)/mul")
    assert p.search("jit(step)/gdn/add") and p.search("jit(step)/jvp(gdn)")
    assert not p.search("jit(step)/gdnx/add")
    assert not p.search("jit(step)/my_gdn/add")
    assert scope_time.event_instruction(
        "%fusion.12 = f32[8]{0} fusion(%p), kind=kLoop") == "fusion.12"


def test_cost_model_counts_what_a_token_multiplies():
    import sys

    from chipbench import qwen3_next_cost as cost

    zoo = sys.modules["mxnet_tpu.gluon.model_zoo.qwen3_next"]
    cfg = dict(zoo.QWEN3_NEXT_80B_A3B, num_hidden_layers=4, vocab_size=18992)
    assert cost.layer_kinds(cfg) == ["gdn", "gdn", "gdn", "attn"]
    # the issue's table: one period outside the routed experts 145.2M less
    # norms and vectors, plus the head's slice
    assert abs(cost.matrix_params(cfg) - (145.2e6 + 38.9e6)) < 0.5e6
    assert cost.expert_params(cfg) == 3 * 2048 * 512
    flops = cost.step_flops(cfg, 1, 4096, 4 * 2560)
    dense = 6 * 4096 * cost.matrix_params(cfg)
    assert dense < flops < 1.25 * dense
    # more pairs here, more flops, by 6 x the expert's parameters a pair
    more = cost.step_flops(cfg, 1, 4096, 4 * 2560 + 100)
    assert more - flops == pytest.approx(600 * cost.expert_params(cfg))
