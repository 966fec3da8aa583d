"""Operations and bytes of A.X-K1 serving, counted from its shapes: the
numerators of ``mfu_active.serve_mla``, ``tick_hbm_share.serve_mla`` and
``mla_decode_roofline.serve_mla``, and what the other readers of this
configuration need to know of the run.

As ``granite_hybrid_cost``: the count is by part. Every matrix a token
multiplies outside the routed experts (latent attention's five, the dense
layer's MLP, the shared expert, the router, the head's slice; the embedding's
gather not), the routed experts by the token-expert pairs ACTUALLY computed
here (the program's counter), and causal attention by the keys a query
attends, at the MODEL's widths (a key of ``qk_nope + qk_rope`` numbers and a
value of ``v_head_dim`` a head: what the absorbed form multiplies more, 576 +
512 a head, is the program's choice and not counted). 2 flops a multiply-add,
forward only.
"""
from chipbench.granite_hybrid_cost import stats_window, window_means

__all__ = ["note_run", "last_run", "stats_window", "window_means",
           "attention_params", "matrix_params", "expert_params",
           "window_flops", "tick_bytes", "latent_row", "mla_decode_floor_s",
           "kernel_seconds"]

_LAST = {}
MLA_KERNEL = "mxtpu_mla_decode"


def note_run(cfg, experts_held, itemsize, net=None):
    """The task notes what it built; the readers of this configuration's
    metrics, which get only the runner's observations, read it here. ``net``
    is held so that it outlives the runner."""
    _LAST.update(cfg=dict(cfg), experts_held=tuple(experts_held),
                 itemsize=int(itemsize), net=net)


def last_run():
    return dict(_LAST) if _LAST else None


def latent_row(cfg):
    """(row width, value width) of what a cache holds a position and layer."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
            cfg["kv_lora_rank"])


def attention_params(cfg):
    """Latent attention's matrices (the two latent norms' vectors not)."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (D * rq + rq * H * (dn + dr) + D * (rkv + dr)
            + rkv * H * (dn + dv) + H * dv * D)


def n_dense(cfg):
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def n_routed(cfg):
    return cfg["num_hidden_layers"] - n_dense(cfg)


def matrix_params(cfg):
    """Multiply-adds a token makes outside the routed experts, all layers
    held and the head's slice (``cfg``: as the model was built, so
    ``num_hidden_layers`` and ``vocab_size`` are what is held)."""
    D = cfg["hidden_size"]
    shared = 3 * D * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + n_dense(cfg) * 3 * D * cfg["intermediate_size"]
            + n_routed(cfg) * (shared + D * cfg["n_routed_experts"])
            + cfg["vocab_size"] * D)


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def window_flops(cfg, tokens, pairs_here, prompt_tokens, prefills,
                 positions_attended):
    """Model flops of a window: ``tokens`` forwarded in all (prompt and
    generated), ``pairs_here`` token-expert pairs computed here (counter),
    causal attention over ``prefills`` prompts of ``prompt_tokens`` in all
    (counted at their mean length: by Jensen a lower bound of the sum of
    squares) and ``positions_attended``, the sum over the window's ticks of
    the live slots' lengths."""
    matrices = 2.0 * matrix_params(cfg) * tokens
    experts = 2.0 * expert_params(cfg) * pairs_here
    mean = prompt_tokens / prefills if prefills else 0.0
    attended = positions_attended + prefills * mean * mean / 2.0
    # q.k and p.v: 2 flops a query, head, key attended and entry of the
    # key (d_n + d_r) and of the value (d_v)
    a_pair = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    attention = 2.0 * a_pair * attended * cfg["num_hidden_layers"]
    return matrices + experts + attention


def tick_bytes(cfg, itemsize, experts_touched, pages_live, page_tokens):
    """Bytes ONE tick cannot avoid moving: the weights outside the routed
    experts once (the head's slice with them), the held experts that got a
    token (``experts_touched``: summed over the routed layers, from the
    counter), the live latent rows read once a layer. Activations, the
    router's float32 copies, the rows written and whatever the compiler adds
    are NOT counted: the share of the memory's rate this gives cannot pass
    100."""
    weights = (matrix_params(cfg) + expert_params(cfg) * experts_touched) \
        * itemsize
    rows = pages_live * page_tokens * latent_row(cfg)[0] * itemsize \
        * cfg["num_hidden_layers"]
    return weights + rows


def mla_decode_floor_s(cfg, itemsize, pages_live, page_tokens, peak):
    """The least time the absorbed decode attention of ONE tick (all layers)
    can take on a device of ``peak`` (``peaks.peaks``): the larger of its
    bytes (every live page's rows read once a layer; a page is read whole)
    over the memory's rate and its flops (every head's 576-wide score and
    512-wide sum over those positions) over the bf16 peak. The same count
    whatever implements it."""
    width, value = latent_row(cfg)
    positions = pages_live * page_tokens
    L = cfg["num_hidden_layers"]
    nbytes = positions * width * itemsize * L
    flops = 2.0 * cfg["num_attention_heads"] * (width + value) * positions * L
    return max(nbytes / peak["hbm_bytes_per_s"],
               flops / peak["bf16_flops_per_s"])


def kernel_seconds(kernel=MLA_KERNEL):
    """Device seconds of the Pallas kernel ``kernel`` (its instructions are
    named after it: ``mxtpu_mla_decode.3``) in the newest trace, all serving
    programs together, by self time; None without a trace or where no such
    instruction ran (a program without the kernel, as every commit before
    it)."""
    from chipbench import program_spans, scope_time_serve, trace_reduce

    path = trace_reduce.newest_xplane(program_spans.default_trace_dir())
    by = scope_time_serve.seconds_by_module(
        trace_reduce.load_xplane(path)) if path else None
    if not by:
        return None
    found = [s for insts in by.values() for inst, s in insts.items()
             if inst.split(".")[0] == kernel]
    return sum(found) if found else None
