"""The A.X-K1 serving cell at a tiny size on the CPU: ``serve_decode`` runs it
unchanged through the new task, the task holds the cache views (a latent
cache: prefill expanded, ticks absorbed) to the plain reference and tells
planted faults and a lower-precision control from the system, and every
reader the cell brings returns a number on its observations. The tiny
configuration and traffic are written here, as NEW files of a copy of the
benchmark: nothing that is there is edited."""
import copy
import json
import os
import shutil
import sys
import time

import pytest

from chipbench_paths import BENCH

CELL = "axk1-tiny.decode-tiny-64"
REAL = "A.X-K1.decode-saturated-64"
# the metrics this cell brought, and the shared serving metrics whose
# readers count by the cost module of the cell's task or by its scopes
NEW_METRICS = ("mfu_active.serve", "tick_hbm_share.serve",
               "attn_time_share.serve", "mla_decode_roofline.serve_mla",
               "cache_bytes_per_token.serve_mla",
               "experts_time_share.serve")
# the accepted serving metrics whose readers know no model and always find
# something to read in a traced run of this cell
SHARED_METRICS = (
    "slot_occupancy.serve", "kv_pages_live_share.serve",
    "tick_device_ms.serve", "warmup_s.serve", "pallas_time_share.serve",
    "device_idle_share.serve", "peak_hbm_gib.serve",
    "engine_host_ms_per_tick.serve")


def _real_config():
    with open(os.path.join(BENCH, "configs", "A.X-K1.json")) as fh:
        return json.load(fh)


def _tiny_config():
    """The real file with tiny sizes: every key the task reads stays."""
    config = _real_config()
    config.update(
        name="axk1-tiny", hidden_size=32, num_hidden_layers=3,
        layers_held=[0, 3], intermediate_size=48, num_attention_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, moe_intermediate_size=16,
        num_experts_per_tok=4, router_num_experts=16, experts_held=[4, 12],
        n_routed_experts=8, vocab_size=96, n_vocab=96,
        vocab_rows_held=[0, 96], max_position_embeddings=1024,
        rope_scaling=dict(config["rope_scaling"], factor=8,
                          original_max_position_embeddings=32),
        dtype="float32", logits_tolerance=0.001, logits_rms_tolerance=0.001,
        rows_tolerance=0.001,
        views_check={"ticks": 4, "max_prefix": 64, "min_bucket": 16,
                     "page_tokens": 16})
    return config


TRAFFIC = {
    "runner": "serve_decode", "clients": 4,
    "engine": {"num_slots": 4, "max_len": 128, "max_prompt_len": 64,
               "min_prompt_bucket": 16, "prefill_batch": 1,
               "page_tokens": 16, "prefix_cache": False},
    "request_pool": 24, "pool_repeats": 400, "shape_seed": 1,
    "prompt_len": {"dist": "loguniform", "min": 5, "max": 64},
    "output_len": {"dist": "uniform", "min": 4, "max": 24},
    "tokens": {"zipf_exponent": 1.0, "perm_seed": 0},
    "ramp_seconds": 0.5, "trace_seconds": 0.5,
    "check_requests": 4, "check_pad_to": 128, "chosen_logit_tolerance": 0.01,
}


def _task():
    tasks = os.path.join(BENCH, "tasks")
    sys.path.insert(0, tasks)
    try:
        import axk1_lm
    finally:
        sys.path.remove(tasks)
    return axk1_lm


@pytest.fixture(scope="module")
def bench(manifest, tmp_path_factory):
    """(bench_dir, manifest) with the tiny cell beside the real one."""
    bench_dir = str(tmp_path_factory.mktemp("axk1_bench") / "chip")
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns(
        ".cache", ".work", "__pycache__"))
    for kind, name, body in (
            ("configs", "axk1-tiny", _tiny_config()),
            ("traffic", "decode-tiny-64", TRAFFIC)):
        path = os.path.join(bench_dir, kind, name + ".json")
        assert not os.path.exists(path)
        with open(path, "w") as fh:
            json.dump(body, fh)
    merged = copy.deepcopy(manifest)
    merged["configs"].append({"name": "axk1-tiny", "source": "tests only",
                              "reduced": [],
                              "file": "configs/axk1-tiny.json",
                              "why": "CPU tests"})
    merged["workloads"].append({"name": CELL, "config": "axk1-tiny",
                                "traffic": "decode-tiny-64", "chips": 1,
                                "why": "serve_decode with the A.X-K1 task"})
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
    obs = found["runner"].run(found["config"], found["traffic"], 2**31 + 36,
                              2.0, False, env)
    return obs, found


def test_the_real_cell_is_in_the_manifest_as_the_issue_gives_it(manifest):
    from chipbench import harness

    cell = harness.find_cell(manifest, REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "A.X-K1", "decode-saturated-64", 1)
    configs = {c["name"]: c for c in manifest["configs"]}
    assert configs["A.X-K1"]["source"] == \
        "https://huggingface.co/skt/A.X-K1/blob/main/config.json"
    mix = harness.resolve(manifest, REAL, BENCH)["traffic"]
    assert mix["runner"] == "serve_decode" and mix["clients"] == 64
    assert mix["engine"] == {
        "num_slots": 64, "max_len": 2560, "max_prompt_len": 2048,
        "min_prompt_bucket": 256, "prefill_batch": 1, "page_tokens": 128,
        "prefix_cache": False}
    assert mix["prompt_len"] == {"dist": "loguniform", "min": 256,
                                 "max": 2048}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert (mix["ramp_seconds"], mix["trace_seconds"],
            mix["check_requests"], mix["check_pad_to"]) == (6.0, 3.0, 4, 2560)
    assert mix["request_pool"] * mix["pool_repeats"] >= 3072
    assert mix["tokens"]["zipf_exponent"] == 1.0
    assert len(mix["chosen_logit_tolerance_why"]) > 40
    # the prefill ladder: four programs
    from mxnet_tpu.serve.bucketing import bucket_ladder

    assert bucket_ladder(2048, min_bucket=256) == [256, 512, 1024, 2048]
    # looked up by name: later cells and metrics are appended, and a later
    # metric may list this cell too
    listed = {m["name"] for m in manifest["per_layer"]
              if REAL in m.get("workloads", ())}
    assert set(NEW_METRICS) | set(SHARED_METRICS) <= listed
    for m in manifest["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s",):
            assert REAL in m["workloads"]
        if m["name"] in ("itl_ms_p95", "train_tokens_per_s"):
            assert REAL not in m["workloads"]
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert REAL in m["workloads"]
            assert m["moves"] == "serve_tokens_per_s"
            assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                               m["name"] + ".py"))


def test_the_configuration_keeps_every_published_width():
    zoo = sys.modules["mxnet_tpu.gluon.model_zoo.axk1"]
    config = _real_config()
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in zoo.AX_K1.items():
        if key in reduced:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        rows = [json.loads(line) for line in fh]
    (row,) = [r for r in rows if r["name"] == "A.X-K1"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert (config["published"][key] if key in reduced
                else config[key]) == value, key
    assert config["router_num_experts"] == 192
    assert config["experts_held"] == [0, config["n_routed_experts"]] \
        == [0, 12]
    assert config["layers_held"] == [0, config["num_hidden_layers"]] \
        == [0, 6]
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["n_vocab"] == config["vocab_size"] \
        == config["vocab_rows_held"][1]
    assert config["dtype"] == "bfloat16" and config["task"] == "axk1_lm"
    assert "16 chips" in config["deployment"] and len(config["assumed"]) >= 8
    for limit in ("logits_tolerance", "logits_rms_tolerance",
                  "routing_margin", "rows_tolerance"):
        assert config[limit] > 0 and len(config[limit + "_why"]) > 40


# multiply-adds a token makes outside the routed experts: six attentions, the
# dense MLP, five shared experts and routers, the head's slice
OUTSIDE = 6 * 101_122_048 + 3 * 7168 * 18432 + 5 * 45_416_448 + 20480 * 7168
COUNTS = {
    # the issue's table, by part
    "latent_attention_matrices": (
        lambda cost, cfg: cost.attention_params(cfg),
        11_010_048 + 18_874_368 + 4_128_768 + 8_388_608 + 58_720_256),
    "one_routed_expert": (lambda cost, cfg: cost.expert_params(cfg),
                          3 * 7168 * 2048),
    "outside_the_experts": (
        lambda cost, cfg: cost.matrix_params(cfg),
        6 * 101_122_048 + 3 * 7168 * 18432
        + 5 * (44_040_192 + 1_376_256) + 20480 * 7168),
    "latent_row": (lambda cost, cfg: cost.latent_row(cfg), (576, 512)),
    "layers": (lambda cost, cfg: (cost.n_dense(cfg), cost.n_routed(cfg)),
               (1, 5)),
    # a tick's floor at 11 experts touched a routed layer, 512 live pages:
    # the weights outside the experts once, 55 experts, 512 x 128 rows of
    # 1152 bytes a layer
    "tick_bytes": (
        lambda cost, cfg: cost.tick_bytes(cfg, 2, 55, 512, 128),
        2 * OUTSIDE + 2 * 55 * 44_040_192 + 512 * 128 * 1152 * 6),
    # model flops: 100 tokens of which a 60-token prompt, 40 pairs, 1000
    # positions attended by the ticks
    "window_flops": (
        lambda cost, cfg: cost.window_flops(cfg, 100, 40, 60, 1, 1000),
        2.0 * OUTSIDE * 100 + 2.0 * 44_040_192 * 40
        + 2.0 * 64 * 320 * (1000 + 60 * 60 / 2) * 6),
}


@pytest.mark.parametrize("what", list(COUNTS))
def test_the_cost_functions_against_hand_counts(what):
    from chipbench import axk1_cost as cost

    cfg = _task().model_config(_real_config())
    fn, want = COUNTS[what]
    assert fn(cost, cfg) == want


def test_the_held_parameters_are_the_issues_count():
    """4166.3M held = 8.33 GB in bfloat16, from the cost functions and the
    small vectors (norms: two a layer, two latent a layer, the final)."""
    from chipbench import axk1_cost as cost, peaks

    cfg = _task().model_config(_real_config())
    small = 6 * (2 * 7168 + 1536 + 512) + 7168
    n = cost.matrix_params(cfg) + 5 * 12 * cost.expert_params(cfg) \
        + 20480 * 7168 + small
    assert abs(n - 4166.3e6) < 0.1e6, n
    # the absorbed attention is bound by its bytes on a v5e: 120 flops a
    # byte, half the ridge of 240
    peak = peaks.peaks("TPU v5 lite")
    floor = cost.mla_decode_floor_s(cfg, 2, 512, 128, peak)
    assert floor == 512 * 128 * 1152 * 6 / 819e9
    flops = 2.0 * 64 * (576 + 512) * 512 * 128 * 6
    assert 110 < flops / (512 * 128 * 1152 * 6) < 125
    assert flops / 197e12 < floor


def test_serve_decode_runs_the_tiny_cell(bench, run):
    from chipbench import axk1_cost, harness

    obs, found = run
    assert obs["correct"] is True, obs["checks"]
    assert obs["attempted"] > 3 and obs["failed"] == 0
    result = harness.build_result(
        bench[1], CELL, dict(obs, memory_peak_bytes=1), found["readers"],
        False, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert result["checks"]["chosen_logit_gap"] <= 0.01
    noted = axk1_cost.last_run()
    assert noted["experts_held"] == (4, 12) and noted["itemsize"] == 4
    assert noted["cfg"]["n_routed_experts"] == 16
    assert all(p.grad_req == "null"
               for p in noted["net"].collect_params().values())


def _row(seed, n_vocab, real=60, width=128):
    import numpy as onp

    row = onp.zeros((1, width), "int32")
    row[0, :real] = onp.random.RandomState(seed).randint(1, n_vocab, real)
    return row


@pytest.mark.parametrize("fault", [None] + list(
    ("other_slots_rows", "rope_position_zero", "values_all_576_columns",
     "scaling_dropped", "mscale_dropped")))
def test_the_task_holds_the_cache_views_to_the_reference(run, fault):
    """What decides ``correct`` inside the task drives the net through
    ``PrefillView`` and ``TickView`` with two slots live at different
    positions, and holds the logits there and the latent rows the cache is
    left with to the reference's: as it stands all pass; with one slot
    attending the other's rows, rotary position 0 in every tick, the sums
    taken over the whole row, the routed scaling or YaRN's m^2 dropped, the
    task says not correct."""
    from chipbench import axk1_cost

    _, found = run
    task, config = _task(), found["config"]
    assert set(task.FAULTS) >= {fault} - {None}
    net = axk1_cost.last_run()["net"]
    row = _row(3, config["n_vocab"])
    spans = task.check_spans(config, row)
    assert spans == [(56, 4), (24, 4)]
    views = task.views_forward(net, config, row, spans, fault=fault)
    system, chosen = task.system_logits(net, row)
    assert len(chosen) == 2                  # the routed layers
    assert views["chosen"][0].shape == (4, 2, 4)    # ticks, slots, top-k
    assert views["chosen_last"][0].shape == (2, 4)
    assert task.tick_positions(spans) == list(range(55, 60)) \
        + list(range(23, 28))
    out = task.reference_forward(
        net, config, row,
        routing=task.routing_with_ticks(chosen, views, spans))
    said = []
    ok, numbers = task.compare(config, system, out, views, spans,
                               say=said.append)
    assert ok is (fault is None), said
    assert (numbers["views_logits_error"] > config["logits_tolerance"]) \
        is (fault is not None), numbers
    assert numbers["logits_error"] <= config["logits_tolerance"]
    # a fault of the cache's side shows in the rows the later layers are
    # left holding too; the first layer's rows no tick's fault reaches but
    # a wrong rotary position
    if fault in ("other_slots_rows", "rope_position_zero"):
        assert numbers["rows_error"] > config["rows_tolerance"], numbers
    # ... and every fault in the rows the ticks wrote, where a later layer's
    # row is made from what the faulty tick computed before it (a softmax
    # only sharper by m^2 moves them least: its limit is the logits')
    if fault != "mscale_dropped":
        assert (numbers["tick_rows_error"] > config["rows_tolerance"]) \
            is (fault is not None), numbers
    assert ("FAILED" in said[0]) is (fault is not None)
    # the plain forward is as it was after a planted fault
    again, _ = task.system_logits(net, row)
    assert float(abs(again - system).max()) == 0.0


@pytest.mark.parametrize("control", [
    {"dtype": "bfloat16"},
    {"dtype": "bfloat16", "round_to": "float8_e4m3fn"}],
    ids=["bfloat16_everywhere", "float8_matrices"])
def test_a_lower_precision_control_fails_the_tasks_limits(run, control):
    """The reference computed in a precision below the stated one, put in
    the system's place, is not correct by the task's own comparison (at
    this size in float32 the system reads 1e-6 and the limits are 1e-3; on
    the chip the limits lie between the two readings, PERF.md section 6)."""
    from chipbench import axk1_cost

    _, found = run
    task, config = _task(), found["config"]
    net = axk1_cost.last_run()["net"]
    row = _row(4, config["n_vocab"])
    spans = task.check_spans(config, row)
    ctl = task.reference_forward(net, config, row, **control)
    out = task.reference_forward(net, config, row, routing=ctl["chosen"])
    ok, numbers = task.compare(
        config, ctl["logits"], out,
        task.views_of(ctl, spans), spans,
        say=lambda line: None)
    assert ok is False
    assert numbers["rows_error"] > config["rows_tolerance"], numbers
    assert numbers["logits_rms_error"] > config["logits_rms_tolerance"]


def test_a_failed_limit_answers_rolled_logits(run, monkeypatch):
    import jax.numpy as jnp
    from chipbench import axk1_cost

    _, found = run
    task, config = _task(), found["config"]
    net = axk1_cost.last_run()["net"]
    row = _row(5, config["n_vocab"])
    good = task.reference_logits(net, config, row)
    strict = dict(config, rows_tolerance=0.0)
    bad = task.reference_logits(net, strict, row)
    assert bool(jnp.array_equal(bad, jnp.roll(good, 1, axis=-1)))
    assert not bool(jnp.array_equal(bad, good))


def _stand_in_trace(obs, monkeypatch, kernel=True):
    """The device trace a CPU run has none of: 1 microsecond an instruction
    of the REAL compiled texts, a module at a time, and (``kernel``) the
    absorbed decode kernel's instruction in the tick, as the chip names
    it."""
    from chipbench import scope_time, scope_time_serve

    texts = scope_time_serve.compiled_texts()
    assert texts and any("decode" in name for name in texts)
    by = {module: {inst: 1e-6
                   for inst in scope_time.instruction_scopes(text)}
          for module, text in texts.items()}
    tick = [name for name in texts if "decode" in name][0]
    if kernel:
        by[tick]["mxtpu_mla_decode.1"] = 2e-4
        by[tick]["mxtpu_mla_decode.2"] = 1e-4
    monkeypatch.setattr(scope_time_serve, "seconds_by_module",
                        lambda planes: by)
    monkeypatch.setattr(scope_time_serve.trace_reduce, "newest_xplane",
                        lambda trace_dir: "there")
    monkeypatch.setattr(scope_time_serve.trace_reduce, "load_xplane",
                        lambda path: [{"name": "stand-in", "lines": []}])
    busy = sum(sum(v.values()) for v in by.values())
    return dict(obs, trace={
        "busy_s": busy, "window_s": 1.0, "idle_share": 0.5,
        "custom_call_s": 3e-4,
        "programs": {tick + "(1)": {"count": 9, "total_s": busy,
                                    "median_s": 0.05}}},
        counters=dict(obs["counters"], device_kind="TPU v5 lite"))


def test_every_new_reader_returns_a_number(run, monkeypatch):
    obs, found = run
    traced = _stand_in_trace(obs, monkeypatch)
    values = {name: found["readers"][name].read(traced)
              for name in NEW_METRICS}
    assert all(isinstance(v, float) and v > 0 for v in values.values()), \
        values
    assert values["attn_time_share.serve"] \
        + values["experts_time_share.serve"] < 100.0
    assert values["mfu_active.serve"] < 100.0
    assert values["tick_hbm_share.serve"] < 100.0
    # three layers of one 24-wide float32 row a position, one pool
    assert values["cache_bytes_per_token.serve_mla"] == 3 * 24 * 4
    # the kernel's floor: the live pages' rows once a layer over the HBM
    # rate, nine ticks, over its 3e-4 s
    from chipbench import axk1_cost as cost, peaks

    window = cost.stats_window(obs)
    _, pages = cost.window_means(obs, window[1])
    floor = cost.mla_decode_floor_s(cost.last_run()["cfg"], 4, pages, 16,
                                    peaks.peaks("TPU v5 lite"))
    assert values["mla_decode_roofline.serve_mla"] == pytest.approx(
        100.0 * floor * 9 / 3e-4)


def test_the_roofline_reader_finds_nothing_without_the_kernel(run,
                                                              monkeypatch):
    obs, found = run
    traced = _stand_in_trace(obs, monkeypatch, kernel=False)
    assert found["readers"]["mla_decode_roofline.serve_mla"] \
        .read(traced) is None
    assert found["readers"]["attn_time_share.serve"].read(traced) > 0


def test_the_new_readers_return_nothing_on_a_program_without_the_counters(
        run, monkeypatch):
    """A program without the stats log, the compiled-text accessor or the
    scopes, as the parent commit: None, no raise."""
    from chipbench import axk1_cost, scope_time_serve

    from mxnet_tpu.serve.decode import engine, programs

    obs, found = run
    traced = dict(obs, trace={"busy_s": 1.0, "window_s": 1.0,
                              "idle_share": 0.0, "programs": {}},
                  counters=dict(obs["counters"], device_kind="TPU v5 lite"))
    # the parent's readings: no sizes of the cache among them
    log = [{k: v for k, v in r.items() if k not in ("cache_bytes",
                                                    "kv_pages")}
           for r in engine.stats_log()]
    with monkeypatch.context() as m:
        m.setattr(engine, "stats_log", lambda: log)
        assert found["readers"]["cache_bytes_per_token.serve_mla"] \
            .read(traced) is None
    monkeypatch.delattr(engine, "stats_log")
    monkeypatch.delattr(programs, "compiled_modules")
    assert axk1_cost.stats_window(obs) is None
    assert scope_time_serve.compiled_texts() is None
    for name in NEW_METRICS:
        assert found["readers"][name].read(traced) is None, name
    # and with no run noted at all
    monkeypatch.setattr(axk1_cost, "_LAST", {})
    for name in NEW_METRICS:
        assert found["readers"][name].read(obs) is None, name
