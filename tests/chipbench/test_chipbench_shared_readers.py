"""The shared serving readers count by the cost module of the task that
noted its run (``chipbench/task_cost.py``). On one recorded observation,
with the Granite and the A.X-K1 cost modules noted in turn, each reads what
the reader it replaced read: Granite's ``mfu_active.serve``,
``tick_hbm_share.serve`` and ``held_experts_touched_share.serve`` as their
former selves, which imported ``granite_hybrid_cost``; the A.X-K1 cell's
``mfu_active.serve``, ``tick_hbm_share.serve``, ``experts_time_share.serve``
and ``attn_time_share.serve`` as the ``*.serve_mla`` doubles they replaced.
The former formulas are written out here, to the last digit."""
import pytest

from chipbench_paths import BENCH
from chipbench import harness

TICK = "jit_mxtpu_serve_decode_k1(7)"
# the engine's ``stats()`` at the two ends of a 3 s window of a saturated
# routed serving cell (an A.X-K1-sized run: 113 ticks, 22 prefills), and
# the runner's observations of that window
FIRST = {"engine": 1, "t": 100.0, "ticks": 1000, "tokens": 60000,
         "prompt_tokens": 150000, "prefills": 200, "moe_pairs_here": 180000,
         "moe_experts_touched": 55000, "num_slots": 64, "page_tokens": 128}
LAST = dict(FIRST, t=103.0002, ticks=1113, tokens=67232,
            prompt_tokens=167248, prefills=222, moe_pairs_here=205120,
            moe_experts_touched=61305)
OBS = {
    "counters": {"ticks": 113, "slot_occupancy": 0.99694,
                 "kv_pages_live": [540, 546, 551, 549],
                 "device_kind": "TPU v5 lite"},
    "spans": {"window_s": 3.0},
    "trace": {"busy_s": 2.9989, "window_s": 3.0, "idle_share": 0.00037,
              "programs": {
                  TICK: {"count": 113, "total_s": 1.5617, "median_s": 0.01382},
                  "jit_mxtpu_serve_prefill_b1_t2048(9)": {
                      "count": 22, "total_s": 1.2761, "median_s": 0.058}}},
}
CONFIGS = {"granite_hybrid_cost": ("granite_hybrid_lm", "granite-4.0-h-small"),
           "axk1_cost": ("axk1_lm", "A.X-K1")}
SCOPE_SECONDS = {("attn",): 0.7083, ("router", "experts"): 1.7306}


@pytest.fixture
def note(monkeypatch):
    """Notes a cost module's run as its task does (the configuration's
    model arguments, its held experts, bfloat16), on a clean slate."""
    from chipbench import axk1_cost, granite_hybrid_cost, task_cost

    from mxnet_tpu.serve.decode import engine

    monkeypatch.setattr(engine, "stats_log", lambda: [FIRST, LAST])
    monkeypatch.setattr(task_cost, "_NOTED", [])
    costs = {"granite_hybrid_cost": granite_hybrid_cost,
             "axk1_cost": axk1_cost}
    for cost in costs.values():
        monkeypatch.setattr(cost, "_LAST", {})

    def noting(name):
        task, config = CONFIGS[name]
        config = harness.load_json(BENCH, "configs", config)
        cfg = harness.load_module(BENCH, "tasks", task).model_config(config)
        costs[name].note_run(cfg, config["experts_held"], 2)
        return costs[name]

    return noting


def _read(name, obs=OBS):
    return harness.load_module(BENCH, "layer_metrics", name).read(obs)


# the former readers, as they were, the cost module named

def _former_mfu(cost, obs):
    run, window = cost.last_run(), cost.stats_window(obs)
    if not run or not window or "moe_pairs_here" not in window[1]:
        return None
    first, last = window
    delta = {k: last[k] - first[k] for k in (
        "tokens", "prompt_tokens", "prefills", "ticks", "moe_pairs_here")}
    live, pages = cost.window_means(obs, last)
    attended = delta["ticks"] * max(
        0.0, last["page_tokens"] * (pages - 0.5 * live))
    flops = cost.window_flops(
        run["cfg"], delta["tokens"] + delta["prompt_tokens"],
        delta["moe_pairs_here"], delta["prompt_tokens"], delta["prefills"],
        attended)
    return 100.0 * flops / obs["spans"]["window_s"] / 197e12


def _touched(cost, obs):
    first, last = cost.stats_window(obs)
    return (last["moe_experts_touched"] - first["moe_experts_touched"]) \
        / (last["ticks"] - first["ticks"]), last


def _former_tick_hbm_granite(cost, obs):
    touched, last = _touched(cost, obs)
    live, pages = cost.window_means(obs, last)
    floor_s = cost.tick_bytes(cost.last_run()["cfg"], 2, touched, live,
                              pages, last["page_tokens"]) / 819e9
    return 100.0 * floor_s / 0.01382


def _former_tick_hbm_axk1(cost, obs):
    touched, last = _touched(cost, obs)
    _, pages = cost.window_means(obs, last)
    cfg = cost.last_run()["cfg"]
    # ``axk1_cost.tick_bytes`` as it was: the weights outside the routed
    # experts once, the experts touched, the live latent rows once a layer
    nbytes = (cost.matrix_params(cfg) + cost.expert_params(cfg) * touched) \
        * 2 + pages * last["page_tokens"] * cost.latent_row(cfg)[0] * 2 \
        * cfg["num_hidden_layers"]
    return 100.0 * nbytes / 819e9 / 0.01382


def _former_held_touched_granite(cost, obs):
    run = cost.last_run()
    first, last = cost.stats_window(obs)
    lo, hi = run["experts_held"]
    slots = (last["ticks"] - first["ticks"]) \
        * len(run["cfg"]["layer_types"]) * (hi - lo)
    return 100.0 * (last["moe_experts_touched"]
                    - first["moe_experts_touched"]) / slots


def test_granites_readers_read_as_they_did(note):
    note("axk1_cost")
    cost = note("granite_hybrid_cost")        # the newest note is the run's
    values = {"mfu_active.serve": _former_mfu(cost, OBS),
              "tick_hbm_share.serve": _former_tick_hbm_granite(cost, OBS),
              "held_experts_touched_share.serve":
                  _former_held_touched_granite(cost, OBS)}
    assert all(isinstance(v, float) and 0 < v < 100 for v in values.values())
    for name, want in values.items():
        assert _read(name) == want, name


def test_the_axk1_cells_merged_names_read_as_their_doubles(note,
                                                           monkeypatch):
    from chipbench import scope_time_serve

    monkeypatch.setattr(scope_time_serve, "scope_seconds",
                        lambda scopes: SCOPE_SECONDS[tuple(scopes)])
    note("granite_hybrid_cost")
    cost = note("axk1_cost")
    busy = OBS["trace"]["busy_s"]
    doubles = {
        "mfu_active.serve": _former_mfu(cost, OBS),
        "tick_hbm_share.serve": _former_tick_hbm_axk1(cost, OBS),
        "attn_time_share.serve": 100.0 * SCOPE_SECONDS[("attn",)] / busy,
        "experts_time_share.serve":
            100.0 * SCOPE_SECONDS[("router", "experts")] / busy}
    assert all(isinstance(v, float) and 0 < v < 100
               for v in doubles.values())
    for name, want in doubles.items():
        assert _read(name) == want, name
    # the routed layers are the cost module's: five of A.X-K1's six held
    touched, _ = _touched(cost, OBS)
    assert _read("held_experts_touched_share.serve") \
        == pytest.approx(100.0 * touched / (5 * 12))


def test_the_noted_module_decides(note):
    """The same observation reads otherwise under the other module: the
    readers follow the note, not an import."""
    granite = note("granite_hybrid_cost")
    by_granite = _read("mfu_active.serve"), _read("tick_hbm_share.serve")
    axk1 = note("axk1_cost")
    by_axk1 = _read("mfu_active.serve"), _read("tick_hbm_share.serve")
    assert by_granite == (_former_mfu(granite, OBS),
                          _former_tick_hbm_granite(granite, OBS))
    assert by_axk1 == (_former_mfu(axk1, OBS),
                       _former_tick_hbm_axk1(axk1, OBS))
    assert by_granite[0] != by_axk1[0] and by_granite[1] != by_axk1[1]


@pytest.mark.parametrize("name", ["mfu_active.serve", "tick_hbm_share.serve",
                                  "held_experts_touched_share.serve"])
def test_no_note_no_reading(note, name):
    assert _read(name) is None
