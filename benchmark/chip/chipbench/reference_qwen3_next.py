"""Plain float32 ``jax.numpy`` reference of the Qwen3-Next forward pass
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, ``model_type``
``qwen3_next``): Gated DeltaNet layers and gated softmax attention layers in
periods of ``full_attention_interval``, each followed by a mixture of experts
with one shared expert, zero-centred RMSNorm, an untied head.

No kernels, no chunks, no sorting: the delta rule is the recurrence itself,
one position at a time (``lax.scan``); attention is dense softmax, a head and
a block of queries at a time (``lax.map``) so that 4096 x 4096 scores never
exist at once; the experts are a loop (``lax.scan``) over the experts held,
each applied to every token and weighted by the router's choice (0 where it
was not chosen). Every
matmul runs under ``jax.default_matmul_precision("highest")``. It takes the
system's own seeded weights by the names ``net.collect_params()`` gives.

The chip's share. ``experts_held = (lo, hi)`` says which of the router's
``num_experts`` the expert arrays hold (array row j is expert lo + j). The
router keeps its full width and its full top-k, the weights are those of the
full top-k, and the sum runs over the chosen experts that are held: what the
absent experts would add is left out, as in the program. The vocabulary held
is whatever the embedding and the head have rows for.

Departures from the published code, which the model file shares:

- ``in_proj_qkvz`` and ``in_proj_ba`` are split flat, ``[q | k | v | z]`` and
  ``[b | a]``, not interleaved by key-head group (a permutation of the
  projection's rows; with seeded random weights the same distribution);
- the routed experts' gate and up projections are one array
  ``gate_up`` (experts, hidden, 2 x width);
- no multi-token-prediction module and no auxiliary balancing loss.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-6
Q_BLOCK = 512


def rms_norm(x, w, zero_centred=True):
    y = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    return y * (1.0 + w) if zero_centred else y * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def dense(x, w):
    """The repo's ``Dense`` keeps weights as (out, in)."""
    return x @ w.T


def l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + EPS)


def causal_conv(x, w):
    """x (B, T, C), w (C, K): y[t] = sum_j w[:, j] x[t - (K - 1) + j]."""
    K, T = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * w[:, j] for j in range(K))


def delta_rule_recurrence(q, k, v, g, beta):
    """The rule one position at a time. q, k: (B, T, H, dk) normalised and
    scaled; v: (B, T, H, dv); g, beta: (B, T, H). Returns (B, T, H, dv)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs                 # (B, H, ..)
        S = S * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((B, H, dk, dv), q.dtype), xs)
    return jnp.moveaxis(o, 0, 1)


def gated_delta_net(x, w, cfg):
    B, T, _ = x.shape
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kw, vw = Hk * dk, Hv * dv
    q, k, v, z = jnp.split(dense(x, w["in_proj_qkvz.weight"]),
                           [kw, 2 * kw, 2 * kw + vw], axis=-1)
    b, a = jnp.split(dense(x, w["in_proj_ba.weight"]), 2, axis=-1)
    qkv = silu(causal_conv(jnp.concatenate([q, k, v], -1), w["conv_weight"]))
    q, k, v = jnp.split(qkv, [kw, 2 * kw], axis=-1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"])
    reps = Hv // Hk
    q = jnp.repeat(l2norm(q.reshape(B, T, Hk, dk)) * dk ** -0.5, reps, axis=2)
    k = jnp.repeat(l2norm(k.reshape(B, T, Hk, dk)), reps, axis=2)
    o = delta_rule_recurrence(q, k, v.reshape(B, T, Hv, dv), g, beta)
    y = rms_norm(o, w["norm_weight"], zero_centred=False) \
        * silu(z.reshape(B, T, Hv, dv))
    return dense(y.reshape(B, T, vw), w["out_proj.weight"])


def rope(x, theta, rotary_dim):
    """Rotate-half RoPE on the first ``rotary_dim`` of the last axis;
    x: (B, T, H, D), positions 0..T-1."""
    T = x.shape[1]
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]   # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], \
        x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def gated_attention(x, w, cfg):
    B, T, _ = x.shape
    H, Hkv, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    rot = int(D * cfg["partial_rotary_factor"])
    qg = dense(x, w["q_proj.weight"]).reshape(B, T, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = dense(x, w["k_proj.weight"]).reshape(B, T, Hkv, D)
    v = dense(x, w["v_proj.weight"]).reshape(B, T, Hkv, D)
    q = rope(rms_norm(q, w["q_norm.weight"]), cfg["rope_theta"], rot)
    k = rope(rms_norm(k, w["k_norm.weight"]), cfg["rope_theta"], rot)
    group = H // Hkv
    block = min(Q_BLOCK, T)
    pad = (-T) % block

    def one_head(h):
        qh = jnp.pad(q[:, :, h], ((0, 0), (0, pad), (0, 0)))     # (B, T+, D)
        kh, vh = k[:, :, h // group], v[:, :, h // group]        # (B, T, D)

        def one_block(s):
            qb = lax.dynamic_slice_in_dim(qh, s, block, axis=1)
            sc = jnp.einsum("bqd,bkd->bqk", qb, kh) * D ** -0.5
            keep = (s + jnp.arange(block))[:, None] >= jnp.arange(T)[None]
            p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p, vh)

        o = lax.map(one_block, jnp.arange(0, T + pad, block))    # (n,B,b,D)
        return jnp.moveaxis(o, 0, 1).reshape(B, T + pad, D)[:, :T]

    attn = jnp.moveaxis(lax.map(one_head, jnp.arange(H)), 0, 2)  # (B,T,H,D)
    out = (attn * jax.nn.sigmoid(gate)).reshape(B, T, H * D)
    return dense(out, w["o_proj.weight"])


def expert(x, gate_up, down):
    """SwiGLU at the expert's width; gate_up (hidden, 2F), down (F, hidden)."""
    h = x @ gate_up
    F = down.shape[0]
    return (silu(h[..., :F]) * h[..., F:]) @ down


def sparse_moe(x, w, cfg, experts_held, chosen=None, tie_ratio=0.0):
    """Returns ``(y, margin, chosen)``. ``chosen`` (N, k): the program's
    choice of experts, used in place of the reference's own top-k ONLY for
    the tokens whose choice is a near-tie: those whose (k+1)-th probability
    reaches ``tie_ratio`` of the k-th. There rounding flips the choice, and
    one flipped expert moves that token's logits by more than all other
    rounding does. Every other token gets the reference's own choice, so a
    program that chose otherwise there is off by a whole expert. The
    weights are always the reference's own probabilities. ``margin``: the
    smallest, over tokens and experts used, of the expert's probability
    over the k-th largest: 1 where the choice is the reference's own, and
    how far below the reference's threshold the worst expert taken from the
    program lies otherwise. ``chosen`` returned: the experts used."""
    B, T, D = x.shape
    k = cfg["num_experts_per_tok"]
    xf = x.reshape(B * T, D)
    p = jax.nn.softmax(dense(xf, w["router.weight"]), axis=-1)
    top_p, top_i = lax.top_k(p, k + 1)
    near_tie = top_p[:, k:] >= tie_ratio * top_p[:, k - 1:k]
    top_p, top_i = top_p[:, :k], top_i[:, :k]
    chosen = top_i if chosen is None else jnp.where(near_tie, chosen, top_i)
    chosen_p = jnp.take_along_axis(p, chosen, axis=1)
    margin = jnp.min(chosen_p / top_p[:, -1:])
    if cfg["norm_topk_prob"]:
        chosen_p = chosen_p / jnp.sum(chosen_p, -1, keepdims=True)
    lo, hi = experts_held

    def add_expert(y, ew):
        e, gate_up, down = ew
        weight = jnp.sum(jnp.where(chosen == e, chosen_p, 0.0), axis=-1)
        return y + weight[:, None] * expert(xf, gate_up, down), None

    y = jnp.zeros_like(xf)
    if hi > lo:
        y, _ = lax.scan(add_expert, y, (jnp.arange(lo, hi), w["gate_up"],
                                        w["down"]))
    shared = (silu(dense(xf, w["shared_gate_proj.weight"]))
              * dense(xf, w["shared_up_proj.weight"]))
    shared = dense(shared, w["shared_down_proj.weight"])
    y = y + jax.nn.sigmoid(dense(xf, w["shared_gate.weight"])) * shared
    return y.reshape(B, T, D), margin, chosen


def is_full_attention(i, cfg):
    return (i + 1) % cfg["full_attention_interval"] == 0


def _sub(weights, prefix):
    return {k[len(prefix):]: v for k, v in weights.items()
            if k.startswith(prefix)}


@functools.partial(jax.jit, static_argnames=("kind", "cfg_items", "held",
                                             "tie_ratio"))
def _layer(x, w, chosen, kind, cfg_items, held, tie_ratio):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        mixer = gated_attention if kind == "attn" else gated_delta_net
        x = x + mixer(rms_norm(x, w["norm_1.weight"]), _sub(w, "mixer."),
                      cfg)
        y, margin, chosen = sparse_moe(rms_norm(x, w["norm_2.weight"]),
                                       _sub(w, "moe."), cfg, held, chosen,
                                       tie_ratio)
        return x + y, margin, chosen


@jax.jit
def _head(x, norm_w, head_w):
    with jax.default_matmul_precision("highest"):
        return dense(rms_norm(x, norm_w), head_w)


def layer(x, layer_weights, i, cfg, experts_held, chosen=None,
          tie_ratio=0.0):
    """One decoder layer on (B, T, hidden); ``layer_weights`` by the names
    below ``layers.<i>.``. Returns ``(x, margin, chosen)`` (see
    ``sparse_moe``)."""
    kind = "attn" if is_full_attention(i, cfg) else "gdn"
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool))))
    if chosen is not None:
        chosen = jnp.asarray(chosen, jnp.int32)
    return _layer(x, layer_weights, chosen, kind=kind, cfg_items=items,
                  held=tuple(experts_held), tie_ratio=float(tie_ratio))


def forward(weights, cfg, tokens, experts_held=None, routing=None,
            tie_ratio=0.0):
    """``{"logits": (B, T, V_held), "routing_margin": scalar, "chosen":
    the experts used, a layer}``.
    ``weights``: name -> float32 array; ``cfg``: the configuration file's
    keys; ``experts_held``: (lo, hi), default: every expert the arrays
    hold, from 0; ``routing``: one (B*T, k) array of the program's chosen
    experts a layer, taken where the reference's own choice is a near-tie
    by ``tie_ratio`` (``sparse_moe``; 0 takes them everywhere); default:
    the reference's own top-k (margin 1)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    if experts_held is None:
        experts_held = (0, weights["layers.0.moe.gate_up"].shape[0])
    x = weights["embed.weight"][tokens]
    margins, used = [], []
    for i in range(cfg["num_hidden_layers"]):
        x, margin, chosen = layer(x, _sub(weights, f"layers.{i}."), i, cfg,
                                  experts_held,
                                  None if routing is None else routing[i],
                                  tie_ratio)
        margins.append(margin)
        used.append(chosen)
    return {"logits": _head(x, weights["norm_f.weight"],
                            weights["lm_head.weight"]),
            "routing_margin": jnp.min(jnp.stack(margins)), "chosen": used}


def loss(weights, cfg, tokens, labels, experts_held=None):
    """Mean next-token cross entropy over the vocabulary held, as
    ``SoftmaxCrossEntropyLoss`` followed by ``mean``."""
    logits = forward(weights, cfg, tokens, experts_held)["logits"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.asarray(labels, jnp.int32)[..., None], axis=-1)
    return -jnp.mean(picked)
