"""Plain float32 ``jax.numpy`` references of the transformer forward pass.

One function, ``forward``, serves both families the benchmark runs: the
causal pre-LN block of GPT-2 (Radford et al. 2019) and the post-LN block of
BERT with its masked-LM and next-sentence heads (Devlin et al. 2018,
arXiv:1810.04805). No kernels, no cache, no batching tricks: dense
attention over the whole sequence, every matmul under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes). It takes the system's own seeded weights by
the names ``net.collect_params()`` gives them.

Departures of the repo's model files from the published descriptions, which
the reference follows so that the two can be compared at all:

- GPT-2 (``gluon/model_zoo/gpt.py``): the feed-forward activation is the
  exact erf GELU; OpenAI's code uses the tanh approximation.
- BERT (``gluon/model_zoo/bert.py``): the masked-LM head's LayerNorm uses
  epsilon 1e-5 (the encoder's use the published 1e-12); the token-type
  embedding is added only when token types are given.
"""
import functools
import math

import jax
import jax.numpy as jnp

GPT2 = {
    "family": "gpt2", "pre_ln": True, "causal": True, "eps": 1e-5,
    "tok": "tok_embed.weight", "pos": "pos_embed.weight", "type": None,
    "embed_ln": None,
    "layer": "blocks.{i}.", "ln_attn": "ln_1", "ln_ffn": "ln_2",
}
BERT = {
    "family": "bert", "pre_ln": False, "causal": False, "eps": 1e-12,
    "tok": "bert.word_embed.weight", "pos": "bert.position_embed",
    "type": "bert.token_type_embed.weight", "embed_ln": "bert.embed_ln",
    "layer": "bert.encoder.layers.{i}.", "ln_attn": "attn_ln",
    "ln_ffn": "ffn_ln",
}
SPECS = {"gpt2": GPT2, "bert": BERT}


def layer_norm(x, gamma, beta, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def dense(x, w, b):
    """The repo's ``Dense`` keeps weights as (out, in)."""
    return x @ w.T + b


def attention(x, wqkv, bqkv, wo, bo, heads, causal):
    B, T, U = x.shape
    d = U // heads
    q, k, v = jnp.split(dense(x, wqkv, bqkv), 3, axis=-1)

    def split(t):
        return t.reshape(B, T, heads, d).transpose(0, 2, 1, 3)

    q, k, v = split(q), split(k), split(v)
    s = (q @ k.transpose(0, 1, 3, 2)) / math.sqrt(d)
    if causal:
        keep = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = (p @ v).transpose(0, 2, 1, 3).reshape(B, T, U)
    return dense(o, wo, bo)


@functools.partial(jax.jit, static_argnames=("heads", "pre_ln", "causal",
                                             "eps"))
def block(x, w, heads, pre_ln, causal, eps):
    """One transformer block; ``w`` holds its twelve arrays."""
    with jax.default_matmul_precision("highest"):
        if pre_ln:   # GPT-2: x + f(LN(x))
            h = layer_norm(x, w["ln_attn_g"], w["ln_attn_b"], eps)
            x = x + attention(h, w["wqkv"], w["bqkv"], w["wo"], w["bo"],
                              heads, causal)
            h = layer_norm(x, w["ln_ffn_g"], w["ln_ffn_b"], eps)
            return x + dense(gelu(dense(h, w["w1"], w["b1"])),
                             w["w2"], w["b2"])
        # BERT: LN(x + f(x))
        a = attention(x, w["wqkv"], w["bqkv"], w["wo"], w["bo"], heads,
                      causal)
        x = layer_norm(x + a, w["ln_attn_g"], w["ln_attn_b"], eps)
        f = dense(gelu(dense(x, w["w1"], w["b1"])), w["w2"], w["b2"])
        return layer_norm(x + f, w["ln_ffn_g"], w["ln_ffn_b"], eps)


def _layer_weights(weights, spec, i):
    p = spec["layer"].format(i=i)
    return {
        "wqkv": weights[p + "attn_qkv.weight"],
        "bqkv": weights[p + "attn_qkv.bias"],
        "wo": weights[p + "attn_proj.weight"],
        "bo": weights[p + "attn_proj.bias"],
        "w1": weights[p + "ffn_1.weight"], "b1": weights[p + "ffn_1.bias"],
        "w2": weights[p + "ffn_2.weight"], "b2": weights[p + "ffn_2.bias"],
        "ln_attn_g": weights[p + spec["ln_attn"] + ".gamma"],
        "ln_attn_b": weights[p + spec["ln_attn"] + ".beta"],
        "ln_ffn_g": weights[p + spec["ln_ffn"] + ".gamma"],
        "ln_ffn_b": weights[p + spec["ln_ffn"] + ".beta"],
    }


@functools.partial(jax.jit, static_argnames=("eps",))
def _embed(tok_w, pos_w, type_w, ln, tokens, token_types, eps):
    T = tokens.shape[1]
    x = tok_w[tokens] + pos_w[:T][None]
    if type_w is not None and token_types is not None:
        x = x + type_w[token_types]
    if ln is not None:
        x = layer_norm(x, ln[0], ln[1], eps)
    return x


@jax.jit
def _gpt_head(x, g, b, tok_w):
    with jax.default_matmul_precision("highest"):
        return layer_norm(x, g, b, 1e-5) @ tok_w.T


@jax.jit
def _bert_heads(x, w):
    with jax.default_matmul_precision("highest"):
        pooled = jnp.tanh(dense(x[:, 0], w["pool_w"], w["pool_b"]))
        nsp = dense(pooled, w["nsp_w"], w["nsp_b"])
        h = gelu(dense(x, w["mlm_w"], w["mlm_b"]))
        h = layer_norm(h, w["mlm_g"], w["mlm_beta"], 1e-5)
        return h @ w["tok"].T + w["mlm_bias"], nsp


def forward(weights, family, heads, layers, tokens, token_types=None):
    """Logits of the whole sequence. ``weights``: name -> float32 array.
    Returns ``{"logits": (B, T, V)}`` and, for BERT, ``"nsp": (B, 2)``."""
    spec = SPECS[family]
    tokens = jnp.asarray(tokens, jnp.int32)
    if token_types is not None:
        token_types = jnp.asarray(token_types, jnp.int32)
    ln = None
    if spec["embed_ln"]:
        ln = (weights[spec["embed_ln"] + ".gamma"],
              weights[spec["embed_ln"] + ".beta"])
    type_w = weights[spec["type"]] if spec["type"] else None
    x = _embed(weights[spec["tok"]], weights[spec["pos"]], type_w, ln,
               tokens, token_types, spec["eps"])
    for i in range(layers):
        x = block(x, _layer_weights(weights, spec, i), heads=heads,
                  pre_ln=spec["pre_ln"], causal=spec["causal"],
                  eps=spec["eps"])
    if spec["family"] == "gpt2":
        return {"logits": _gpt_head(x, weights["ln_f.gamma"],
                                    weights["ln_f.beta"],
                                    weights[spec["tok"]])}
    logits, nsp = _bert_heads(x, {
        "pool_w": weights["bert.pooler.weight"],
        "pool_b": weights["bert.pooler.bias"],
        "nsp_w": weights["nsp_classifier.weight"],
        "nsp_b": weights["nsp_classifier.bias"],
        "mlm_w": weights["mlm_transform.weight"],
        "mlm_b": weights["mlm_transform.bias"],
        "mlm_g": weights["mlm_ln.gamma"], "mlm_beta": weights["mlm_ln.beta"],
        "tok": weights[spec["tok"]], "mlm_bias": weights["mlm_decoder_bias"],
    })
    return {"logits": logits, "nsp": nsp}


def n_params(net):
    return sum(int(p.data().size) for p in net.collect_params().values())


def system_weights(net):
    """The system's parameters as raw float32 arrays, by name."""
    return {name: jnp.asarray(p.data()._data, jnp.float32)
            for name, p in net.collect_params().items()}


def logits_error(system_logits, reference_logits):
    """Largest absolute difference, as a share of the reference logits'
    standard deviation (random-weight logits are small, so an absolute
    tolerance would say nothing)."""
    ref = jnp.asarray(reference_logits, jnp.float32)
    err = jnp.max(jnp.abs(jnp.asarray(system_logits, jnp.float32) - ref))
    return float(err / jnp.std(ref))


def chosen_token_gaps(reference_logits, chosen):
    """For each position, how far the reference logit of the chosen token
    lies under the reference maximum, as a share of that position's logit
    standard deviation. 0 where the system chose the reference's argmax; an
    argmax flipped by rounding gives a small gap, a wrong cache a large one.
    ``reference_logits``: (n, V); ``chosen``: n token ids."""
    ref = jnp.asarray(reference_logits, jnp.float32)
    idx = jnp.asarray(chosen, jnp.int32)
    picked = jnp.take_along_axis(ref, idx[:, None], axis=1)[:, 0]
    gap = (ref.max(axis=1) - picked) / ref.std(axis=1)
    return [float(g) for g in gap]
