"""Operations and bytes of Granite-4.0-H serving, counted from its shapes:
the numerators of ``mfu_active.serve`` and ``tick_hbm_share.serve`` in this
configuration's cell, and what the other readers of this configuration need
to know of the run.

``2 x parameters x tokens`` does not fit a routed model: a token multiplies
only the experts it was routed to, and of those only the ones held here. So
the count is by part: every matrix a token multiplies outside the routed
experts (the head's slice included, the embedding's gather not), the routed
experts by the token-expert pairs ACTUALLY computed here (the program's
counter, not the expectation), the state-space recurrence (three
multiply-adds a state element and token: decay, the outer product's add, the
contraction with C; the chunked form's extra products are the program's, not
the model's), and causal attention. 2 flops a multiply-add, forward only.
"""
import sys

from chipbench import task_cost

_LAST = {}
_STATE_MADDS = 3      # a state element and token: decay, add, contract


def note_run(cfg, experts_held, itemsize, net=None):
    """The task notes what it built; the readers of this configuration's
    metrics, which get only the runner's observations, read it here. ``net``
    is held so that it outlives the runner."""
    _LAST.update(cfg=dict(cfg), experts_held=tuple(experts_held),
                 itemsize=int(itemsize), net=net)
    task_cost.note(sys.modules[__name__])


def last_run():
    return dict(_LAST) if _LAST else None


def mixer_params(cfg, kind):
    D = cfg["hidden_size"]
    if kind == "attention":
        kv = cfg["num_key_value_heads"] * (D // cfg["num_attention_heads"])
        return 2 * D * D + 2 * D * kv
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = H * P
    return D * (2 * inner + 2 * N + H) + inner * D


def matrix_params(cfg):
    """Multiply-adds a token makes outside the routed experts, all layers
    held and the head's slice (``cfg``: as the model was built, so
    ``layer_types`` and ``vocab_size`` are what is held)."""
    D = cfg["hidden_size"]
    every = 3 * D * cfg["shared_intermediate_size"] \
        + D * cfg["num_local_experts"]
    return sum(mixer_params(cfg, kind) + every
               for kind in cfg["layer_types"]) + cfg["vocab_size"] * D


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def state_elements(cfg):
    """The SSM state's elements, a sequence and Mamba-2 layer."""
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def n_layers(cfg, kind):
    return sum(k == kind for k in cfg["layer_types"])


def n_routed(cfg):
    """Every layer held routes its tokens."""
    return len(cfg["layer_types"])


def window_flops(cfg, tokens, pairs_here, prompt_tokens, prefills,
                 positions_attended):
    """Model flops of a window: ``tokens`` forwarded in all (prompt and
    generated), ``pairs_here`` token-expert pairs computed here (counter),
    causal attention over ``prefills`` prompts of ``prompt_tokens`` in all
    (counted at their mean length: by Jensen a lower bound of the sum of
    squares) and ``positions_attended``, the sum over the window's ticks of
    the live slots' lengths."""
    D = cfg["hidden_size"]
    matrices = 2.0 * matrix_params(cfg) * tokens
    experts = 2.0 * expert_params(cfg) * pairs_here
    scan = 2.0 * _STATE_MADDS * state_elements(cfg) \
        * n_layers(cfg, "mamba") * tokens
    # q.k and p.v: 4 flops a query, head, head entry and key attended;
    # H x head size = D
    mean = prompt_tokens / prefills if prefills else 0.0
    attended = positions_attended + prefills * mean * mean / 2.0
    attention = 4.0 * D * attended * n_layers(cfg, "attention")
    return matrices + experts + scan + attention


def tick_bytes(cfg, itemsize, experts_touched, live_slots, pages_live,
               page_tokens):
    """Bytes ONE tick cannot avoid moving: the weights outside the routed
    experts once (the head's slice with them), the held experts that got a
    token (``experts_touched``: summed over the routed layers, from the
    counter), the recurrent state of the live slots read and written, the
    live pages read. Activations, the router's float32 copies, the dead
    slots' state and whatever the compiler adds are NOT counted: the share
    of the memory's rate this gives cannot pass 100."""
    D = cfg["hidden_size"]
    weights = (matrix_params(cfg) + expert_params(cfg) * experts_touched) \
        * itemsize
    conv = (cfg["mamba_d_conv"] - 1) * (
        cfg["mamba_n_heads"] * cfg["mamba_d_head"]
        + 2 * cfg["mamba_d_state"]) * itemsize
    state = 2 * live_slots * n_layers(cfg, "mamba") \
        * (4 * state_elements(cfg) + conv)
    kv = cfg["num_key_value_heads"] * (D // cfg["num_attention_heads"])
    pages = pages_live * 2 * kv * page_tokens * itemsize \
        * n_layers(cfg, "attention")
    return weights + state + pages


def stats_window(obs):
    """``(first, last)`` of the engine's ``stats()`` readings that bound the
    runner's window. The runner asks at the window's start and at its end
    and hands on neither reading, only how long the window was and how many
    ticks it held; a sampler, and whoever else, asks as well. So: of the
    newest engine's readings, the pair that lies ``obs["counters"]["ticks"]``
    ticks apart and whose times lie nearest the window's seconds apart (and
    within a second of them). None where the program keeps no such log
    (every commit before it), or no pair matches."""
    try:
        from mxnet_tpu.serve.decode import engine
    except ImportError:
        return None
    log = getattr(engine, "stats_log", None)
    readings = log() if log else []
    ticks = obs["counters"]["ticks"]
    if not readings or not ticks or ticks < 0:
        return None
    mine = [r for r in readings if r["engine"] == readings[-1]["engine"]]
    window_s = obs["spans"]["window_s"]
    pairs = [(abs(b["t"] - a["t"] - window_s), i, a, b)
             for i, a in enumerate(mine) for b in mine[i + 1:]
             if b["ticks"] - a["ticks"] == ticks]
    if not pairs or min(pairs)[0] > 1.0:
        return None
    return min(pairs)[2:]


def window_means(obs, reading):
    """``(live slots, live pages)``, means over the window: the runner's
    ``slot_occupancy`` (a tick) times the engine's ``num_slots``, and its
    once-a-second samples of ``kv_pages_live``."""
    pages = obs["counters"].get("kv_pages_live") or [0]
    live = (obs["counters"].get("slot_occupancy") or 0.0) \
        * reading["num_slots"]
    return live, sum(pages) / len(pages)
