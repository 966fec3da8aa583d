"""The one traffic generator: a traffic file's parameters and ``--seed`` in,
token batches or requests out. A new mix is a new data file, never new code.

Every seed gets the SAME multiset of sizes (drawn from the mix's own
``shape_seed``) in another order, and its own token contents, so two seeds
differ in nothing that changes the amount of work.
"""
import numpy as onp


def rng(seed, stream):
    """A generator for one named stream of one run. ``seed`` is any
    non-negative whole number (the driver's are above 2**31)."""
    return onp.random.default_rng([int(seed), int(stream)])


def seed31(seed, stream):
    """A 31-bit seed for APIs that take one (the framework's ``seed``)."""
    return int(rng(seed, stream).integers(0, 2**31 - 1))


def zipf_probs(vocab, exponent):
    ranks = onp.arange(1, vocab + 1, dtype=onp.float64)
    p = ranks ** -float(exponent)
    return p / p.sum()


def zipf_tokens(gen, vocab, exponent, shape, perm_seed=0):
    """Token ids with Zipf-like frequencies: rank r has probability
    proportional to r**-exponent, and a fixed permutation spreads the ranks
    over the ids so frequent tokens are not the low ids. Uniform tokens
    would leave a language model's loss nowhere to fall but ln(vocab)."""
    cdf = onp.cumsum(zipf_probs(vocab, exponent))
    ranks = onp.searchsorted(cdf, gen.random(shape), side="right")
    ranks = onp.minimum(ranks, vocab - 1)
    perm = onp.random.default_rng(int(perm_seed)).permutation(vocab)
    return perm[ranks].astype(onp.int32)


def _lengths(gen, spec, n):
    """n whole numbers from ``{"dist": "uniform"|"loguniform"|"fixed",
    "min": a, "max": b}`` (both ends included)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    dist = spec["dist"]
    if dist == "fixed" or lo == hi:
        return onp.full(n, lo, dtype=onp.int64)
    if dist == "uniform":
        return gen.integers(lo, hi + 1, size=n)
    if dist == "loguniform":
        v = onp.exp(gen.uniform(onp.log(lo), onp.log(hi + 1), size=n))
        return onp.clip(v.astype(onp.int64), lo, hi)
    raise ValueError(f"unknown length distribution {dist!r}")


def request_shapes(mix, seed):
    """(prompt_len, output_len) pairs in this seed's order: the mix's fixed
    pool of ``request_pool`` pairs, gone through ``pool_repeats`` times,
    each time in another order. A window that consumes about one pool sees
    about the same multiset of sizes whatever the seed."""
    shape_gen = onp.random.default_rng(int(mix["shape_seed"]))
    n = int(mix["request_pool"])
    prompts = _lengths(shape_gen, mix["prompt_len"], n)
    outputs = _lengths(shape_gen, mix["output_len"], n)
    order_gen = rng(seed, 1)
    out = []
    for _ in range(int(mix["pool_repeats"])):
        out += [(int(prompts[i]), int(outputs[i]))
                for i in order_gen.permutation(n)]
    return out


def requests(mix, seed, vocab):
    """``[(prompt tokens, output_len), ...]`` for a serving mix."""
    shapes = request_shapes(mix, seed)
    gen = rng(seed, 2)
    tok = mix["tokens"]
    total = sum(p for p, _ in shapes)
    flat = zipf_tokens(gen, vocab, tok["zipf_exponent"], (total,),
                       tok["perm_seed"])
    out, at = [], 0
    for p, n in shapes:
        out.append(([int(t) for t in flat[at:at + p]], n))
        at += p
    return out


def token_rows(mix, seed, vocab, rows, length, stream=3):
    """``rows`` full rows of ``length`` Zipf-like tokens for a training mix."""
    tok = mix["tokens"]
    return zipf_tokens(rng(seed, stream), vocab, tok["zipf_exponent"],
                       (rows, length), tok["perm_seed"])
