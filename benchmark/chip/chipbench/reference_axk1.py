"""Plain float32 ``jax.numpy`` reference of the A.X-K1 forward pass
(https://huggingface.co/skt/A.X-K1/blob/main/config.json, ``model_type``
``axk1``; the keys are DeepSeek-V3's): multi-head latent attention with YaRN
rotary positions, a leading dense layer, then layers of sigmoid-routed experts
plus a shared expert, plain RMSNorm, an untied head. With ``norm(x) = x /
sqrt(mean(x^2) + eps) * w``::

    h = E[tokens]
    h = h + attn(norm_1(h));  m = norm_2(h)
    h = h + mlp(m)                   (layers below first_k_dense_replace)
    h = h + moe(m) + shared(m)       (the others)
    logits = norm_f(h) W_head^T

No kernels, no cache, no pages, no absorption, no batching, no sorting.
Attention is EXPANDED and dense, a head at a time (``lax.map``): ``c_q =
norm_q(x W_DQ)``, ``[q^n | q^r] = c_q W_UQ`` a head, ``[c' | k^r'] = x W_DKV``,
``c = norm_kv(c')``, ``k_h = [W_UK,h c | rope(k^r')]`` (the rotary part ONE for
all heads), ``v_h = W_UV,h c``, causal softmax over ``s q_h . k_h`` with ``s =
(d_n + d_r)^(-1/2) m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``. YaRN's
inverse frequencies are worked out here from the config's ``rope_scaling``
(``yarn_inv_freq``), rotate-half, angles ``position * inv`` at every position.
The router is ``sigmoid(m W_r^T)`` in float32, the ``num_experts_per_tok``
largest over ALL ``n_routed_experts``, ``g_j = routed_scaling_factor sc_j /
(sum_chosen sc + 1e-20)``; the experts are a loop (``lax.scan``) over the
experts held, each applied to every token and weighted by the router's choice
(0 where it was not chosen); the shared expert is added, ungated. The wide
MLPs (the dense layer's 18432) run in blocks of their width, widened a block
at a time, so that 2560 positions fit beside the model. Every matmul runs
under ``jax.default_matmul_precision("highest")``.

It hands out, a layer, the rows ``[c | k^r]`` of every position: what a
latent cache must hold there.

It takes the system's own seeded weights by the names
``net.collect_params()`` gives, A LAYER AT A TIME: ``layer_weights(i)`` gives
the arrays below ``layers.<i>.``, the routed experts' as they are stored (an
expert is widened alone).

The chip's share: ``experts_held = (lo, hi)`` says which of the router's
``n_routed_experts`` the expert arrays hold; the router keeps its full width
and top-k, the weights are the full top-k's, and the sum runs over the chosen
experts that are held. The vocabulary held is whatever the embedding and the
head have rows for.

``dtype`` other than float32 is the lower-precision CONTROL: weights,
activations, every norm's statistics, the rotary angles with their cos and
sin, and the router's scores in that type, matmuls at the default precision.

Departures from the published code, which the model file shares:
``kv_b_proj`` comes as two arrays (``k_up``, ``v_up``); gate and up matrices
are one array ``[W_g | W_u]``; rotary columns in rotate-half order as stored;
``topk_method`` ``"none"`` is a plain top-k (no groups, no correction bias);
no multi-token-prediction module, no auxiliary loss.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

EXPERT_ARRAYS = ("moe.gate_up", "moe.down")   # widened an expert at a time
# ... and the wide matrices, widened a block of the width at a time
BLOCKED = ("mlp.gate_up.weight", "mlp.down.weight")
_MLP_BLOCK = 4608


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                         + jnp.asarray(eps, x.dtype)) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def dense(x, w):
    """The repo's ``Dense`` keeps weights as (out, in)."""
    return x @ w.T


def yarn_inv_freq(dim, theta, scaling):
    """The ``dim / 2`` inverse frequencies, python floats: ``f_i =
    theta^(-2i/dim)``; ``corr(b) = dim ln(original / (2 pi b)) / (2 ln
    theta)``; ``low = floor(corr(beta_fast))``, ``high =
    ceil(corr(beta_slow))``; ``ramp_i = clip((i - low) / (high - low), 0,
    1)``; ``inv_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i``."""
    half = dim // 2
    f = [theta ** (-2.0 * i / dim) for i in range(half)]
    if scaling is None:
        return f
    original = scaling["original_max_position_embeddings"]

    def corr(turns):
        return dim * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(scaling["beta_fast"])), 0)
    high = min(math.ceil(corr(scaling["beta_slow"])), dim - 1)
    span = (high - low) or 0.001
    return [fi * (1 - r) + fi / scaling["factor"] * r
            for i, fi in enumerate(f)
            for r in [min(max((i - low) / span, 0.0), 1.0)]]


def softmax_scale(cfg):
    scaling = cfg.get("rope_scaling")
    m = 1.0
    if scaling is not None and scaling["factor"] > 1:
        m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, inv_freq):
    """Rotate-half on the whole last axis of ``x`` (B, T, H, D); position t
    is t. Angles, cos and sin in ``x``'s type (float32, or the control's)."""
    T, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(T, dtype=x.dtype)[:, None] \
        * jnp.asarray(inv_freq, x.dtype)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latent_attention(x, w, cfg):
    """Returns the attention's output (B, T, hidden) and the rows ``[c |
    k^r]`` (B, T, kv_lora_rank + qk_rope_head_dim)."""
    B, T, _ = x.shape
    H = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv = yarn_inv_freq(dr, cfg["rope_theta"], cfg.get("rope_scaling"))
    c_q = rms_norm(dense(x, w["q_down.weight"]), w["q_norm.weight"], eps)
    q = dense(c_q, w["q_up.weight"]).reshape(B, T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], inv)], -1)
    ckv = dense(x, w["kv_down.weight"])
    c = rms_norm(ckv[..., :rkv], w["kv_norm.weight"], eps)
    k_r = rope(ckv[..., rkv:].reshape(B, T, 1, dr), inv)
    k_n = dense(c, w["k_up.weight"]).reshape(B, T, H, dn)
    v = dense(c, w["v_up.weight"]).reshape(B, T, H, dv)
    keep = jnp.tril(jnp.ones((T, T), bool))
    scale = jnp.asarray(softmax_scale(cfg), x.dtype)

    def one_head(h):
        kh = jnp.concatenate([k_n[:, :, h], k_r[:, :, 0]], -1)
        s = jnp.einsum("bqd,bkd->bqk", q[:, :, h], kh) * scale
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, v[:, :, h])

    attn = jnp.moveaxis(lax.map(one_head, jnp.arange(H)), 0, 2)  # (B,T,H,dv)
    rows = jnp.concatenate([c, k_r[:, :, 0]], -1)
    return dense(attn.reshape(B, T, H * dv), w["o_proj.weight"]), rows


def swiglu(x, w_in, w_out):
    """``(silu(x W_g) * (x W_u)) W_d`` with ``[W_g | W_u]`` one (hidden, 2F)
    matrix and ``W_d`` (F, hidden)."""
    h = x @ w_in
    F = w_out.shape[0]
    return (silu(h[..., :F]) * h[..., F:]) @ w_out


def swiglu_blocked(x, gate_up, down, dtype, block=_MLP_BLOCK):
    """The same with the matrices as a ``Dense`` stores them (``gate_up``
    (2F, hidden), ``down`` (hidden, F)) and as they are stored: ``block``
    columns of the width are widened to ``dtype`` at a time."""
    F = down.shape[1]
    block = min(block, F)
    if F % block:
        raise ValueError(f"width {F} is no multiple of the block {block}")
    y = jnp.zeros(x.shape, dtype)
    for j in range(0, F, block):
        g = gate_up[j:j + block].astype(dtype)
        u = gate_up[F + j:F + j + block].astype(dtype)
        y = y + dense(silu(dense(x, g)) * dense(x, u),
                      down[:, j:j + block].astype(dtype))
    return y


def routed_plus_shared(x, w, cfg, experts_held, chosen=None, tie_ratio=0.0):
    """Returns ``(y, margin, chosen)``. ``chosen`` (N, k), the program's
    choice of experts, is used in place of the reference's own top-k ONLY
    for the tokens whose (k+1)-th score reaches ``tie_ratio`` of the k-th;
    the weights are always the reference's own. ``margin``: the smallest,
    over tokens and experts used, of the expert's score over the
    reference's k-th largest (1 where both chose alike)."""
    B, T, D = x.shape
    k = cfg["num_experts_per_tok"]
    xf = x.reshape(B * T, D)
    sc = jax.nn.sigmoid(dense(xf, w["router.weight"]))
    if x.dtype == jnp.float32:
        sc = sc.astype(jnp.float32)
    top_p, top_i = lax.top_k(sc, k + 1)
    near_tie = top_p[:, k:] >= tie_ratio * top_p[:, k - 1:k]
    top_p, top_i = top_p[:, :k], top_i[:, :k]
    chosen = top_i if chosen is None else jnp.where(near_tie, chosen, top_i)
    chosen_p = jnp.take_along_axis(sc, chosen, axis=1)
    margin = jnp.min(chosen_p / top_p[:, -1:]).astype(jnp.float32)
    if cfg["norm_topk_prob"]:
        chosen_p = chosen_p / (jnp.sum(chosen_p, -1, keepdims=True)
                               + jnp.asarray(1e-20, sc.dtype))
    chosen_p = (chosen_p * jnp.asarray(cfg["routed_scaling_factor"],
                                       sc.dtype)).astype(x.dtype)
    lo, hi = experts_held

    def add_expert(y, ew):
        e, gate_up, down = ew
        weight = jnp.sum(jnp.where(chosen == e, chosen_p, 0), axis=-1)
        return y + weight[:, None] * swiglu(
            xf, gate_up.astype(x.dtype), down.astype(x.dtype)), None

    y = jnp.zeros_like(xf)
    if hi > lo:
        y, _ = lax.scan(add_expert, y, (jnp.arange(lo, hi), w["gate_up"],
                                        w["down"]))
    y = y + swiglu(xf, w["shared_in.weight"].T, w["shared_out.weight"].T)
    return y.reshape(B, T, D), margin, chosen


def _sub(weights, prefix):
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


def _cfg_items(cfg):
    scaling = cfg.get("rope_scaling")
    return tuple(sorted(
        [(k, v) for k, v in cfg.items()
         if isinstance(v, (int, float, bool, str))]
        + [("rope_scaling", None if scaling is None
            else tuple(sorted(scaling.items())))]))


def _cfg_of(items):
    cfg = dict(items)
    if cfg["rope_scaling"] is not None:
        cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


@functools.partial(jax.jit, static_argnames=("cfg_items", "held",
                                             "tie_ratio", "dtype"))
def _layer(x, w, chosen, cfg_items, held, tie_ratio, dtype):
    cfg = _cfg_of(cfg_items)
    exact = jnp.dtype(dtype) == jnp.float32
    as_stored = EXPERT_ARRAYS + BLOCKED
    w = {k: v if k in as_stored else v.astype(dtype) for k, v in w.items()}
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest" if exact else "default"):
        mixed, rows = latent_attention(
            rms_norm(x, w["norm_1.weight"], eps), _sub(w, "attn."), cfg)
        x = x + mixed
        m = rms_norm(x, w["norm_2.weight"], eps)
        if "mlp.gate_up.weight" in w:
            y = swiglu_blocked(m, w["mlp.gate_up.weight"],
                               w["mlp.down.weight"], jnp.dtype(dtype))
            margin, chosen = jnp.float32(1.0), None
        else:
            y, margin, chosen = routed_plus_shared(
                m, _sub(w, "moe."), cfg, held, chosen, tie_ratio)
        return x + y, margin, chosen, rows


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(x, norm_w, head, eps, dtype):
    exact = jnp.dtype(dtype) == jnp.float32
    with jax.default_matmul_precision("highest" if exact else "default"):
        return dense(rms_norm(x, norm_w.astype(dtype), eps),
                     head.astype(dtype))


def layer(x, layer_weights, cfg, experts_held, chosen=None, tie_ratio=0.0,
          dtype="float32"):
    """One decoder layer on (B, T, hidden); ``layer_weights`` by the names
    below ``layers.<i>.`` (a dense layer has ``mlp.*``, a routed one
    ``moe.*``). Returns ``(x, margin, chosen, rows)`` (see
    ``routed_plus_shared``, ``latent_attention``; a dense layer's margin is
    1 and its ``chosen`` None)."""
    if chosen is not None:
        chosen = jnp.asarray(chosen, jnp.int32)
    return _layer(x, layer_weights, chosen, cfg_items=_cfg_items(cfg),
                  held=tuple(experts_held), tie_ratio=float(tie_ratio),
                  dtype=str(dtype))


def by_layer(weights):
    """``layer_weights`` for a whole dict of name -> array."""
    return lambda i: _sub(weights, f"layers.{i}.")


def forward(embed, head, norm_f, layer_weights, cfg, tokens,
            experts_held=None, routing=None, tie_ratio=0.0, dtype="float32"):
    """``{"logits": (B, T, V_held), "routing_margin": scalar, "chosen": the
    experts used, a ROUTED layer, "rows": (B, T, R) a layer: what a latent
    cache must hold}``.

    ``embed``, ``head`` (V_held, hidden) and ``norm_f`` (hidden,): the rows
    held of the embedding and of the untied head, and the final norm's
    weight; ``layer_weights(i)``: name -> array of layer i (called once a
    layer, its result dropped before the next); ``cfg``: the configuration's
    keys, ``num_hidden_layers`` as held; ``experts_held``: (lo, hi),
    default: every expert the arrays hold, from 0; ``routing``: one (B*T, k)
    array of the program's chosen experts a ROUTED layer, taken where the
    reference's own choice is a near-tie by ``tie_ratio`` (0 takes them
    everywhere); default: the reference's own top-k (margin 1)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.asarray(embed)[tokens].astype(dtype)
    margins, used, rows = [], [], []
    routed = 0
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(i)
        held = experts_held
        chosen = None
        if "moe.gate_up" in w:
            if held is None:
                held = (0, w["moe.gate_up"].shape[0])
            if routing is not None:
                chosen = routing[routed]
            routed += 1
        x, margin, chosen, kept = layer(x, w, cfg, held or (0, 0), chosen,
                                        tie_ratio, dtype)
        del w
        margins.append(margin)
        rows.append(kept)
        if chosen is not None:
            used.append(chosen)
    logits = _head(x, norm_f, head, eps=float(cfg["rms_norm_eps"]),
                   dtype=str(dtype))
    return {"logits": logits, "routing_margin": jnp.min(jnp.stack(margins)),
            "chosen": used, "rows": rows}
