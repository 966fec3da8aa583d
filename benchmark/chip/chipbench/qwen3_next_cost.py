"""Operations of one Qwen3-Next training step, counted from its shapes: the
numerator of ``mfu_active.train``.

``6 x parameters x tokens`` does not fit a routed model: a token multiplies
only the experts it was routed to, and of those only the ones held here. So
the count is by part: every matrix a token multiplies outside the routed
experts (the head's slice included, the embedding's gather not), the routed
experts by the token-expert pairs ACTUALLY computed here (the program's
counter, not the expectation), causal attention, and the chunk products of
the delta rule. Forward 2 flops a multiply-add; backward twice the forward
for matrices (6 in all), 2.5 x for attention (the kernels recompute the
scores: that recomputation is the algorithm's, counted as the flash papers
do). The program's own recomputation (``jax.checkpoint`` around the delta
rule) does NOT count.
"""

_LAST = {}


def note_run(cfg, experts_held, rows, length, net=None):
    """The task notes what it built; the readers of this configuration's
    metrics, which get only the runner's observations, read it here. ``net``
    is held so that its routed layers outlive the runner."""
    _LAST.update(cfg=dict(cfg), experts_held=tuple(experts_held),
                 rows=int(rows), length=int(length), net=net)


def last_run():
    return dict(_LAST) if _LAST else None


def layer_kinds(cfg):
    n, k = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    return ["attn" if (i + 1) % k == 0 else "gdn" for i in range(n)]


def matrix_params(cfg):
    """Multiply-adds a token makes outside the routed experts, all layers
    and the head."""
    D = cfg["hidden_size"]
    kw = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vw = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    gdn = D * (2 * kw + 2 * vw) + D * 2 * cfg["linear_num_value_heads"] \
        + vw * D
    hd = cfg["head_dim"]
    attn = D * 2 * cfg["num_attention_heads"] * hd \
        + 2 * D * cfg["num_key_value_heads"] * hd \
        + cfg["num_attention_heads"] * hd * D
    moe = D * cfg["num_experts"] \
        + 3 * D * cfg["shared_expert_intermediate_size"] + D
    kinds = layer_kinds(cfg)
    return (kinds.count("gdn") * gdn + kinds.count("attn") * attn
            + len(kinds) * moe + D * cfg["vocab_size"])


def expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_flops(cfg, rows, length):
    """Causal softmax attention of every attention layer, forward and
    backward: forward 2 x T^2 x heads x head_dim a row (QK^T and PV over the
    lower triangle), backward 2.5 x that."""
    fwd = 2 * length ** 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    return 3.5 * fwd * rows * layer_kinds(cfg).count("attn")


def delta_rule_chunk_flops(cfg, chunk=64, block=16):
    """Forward flops of one chunk of one value head (``ops/delta_rule.py``):
    the key overlaps, the block merges of the triangular inverse, its
    product with [beta v | beta k decay], the local scores, and the four
    products with the carried state."""
    C, dk, dv = chunk, cfg["linear_key_head_dim"], \
        cfg["linear_value_head_dim"]
    merges, s = 0, block
    while s < C:
        merges += 2 * (2 * s ** 3) * (C // (2 * s))
        s *= 2
    return (2 * C * C * dk            # k k^T
            + merges
            + 2 * C * C * (dv + dk)   # inverse x [u | w]
            + 2 * C * C * dk          # q k^T
            + 2 * C * dk * dv         # w S
            + 2 * C * dk * dv         # q S
            + 2 * C * C * dv          # local x v_new
            + 2 * C * dk * dv)        # k^T v_new


def delta_rule_flops(cfg, rows, length, chunk=64):
    chunks = -(-length // chunk)
    return (3 * delta_rule_chunk_flops(cfg, chunk) * chunks
            * cfg["linear_num_value_heads"] * rows
            * layer_kinds(cfg).count("gdn"))


def step_flops(cfg, rows, length, pairs_here):
    """Model flops of one training step; ``pairs_here``: the token-expert
    pairs computed here in that step, summed over the routed layers."""
    tokens = rows * length
    return (6 * tokens * matrix_params(cfg)
            + 6 * expert_params(cfg) * pairs_here
            + attention_flops(cfg, rows, length)
            + delta_rule_flops(cfg, rows, length))
