"""The one traffic generator: seeds, sizes and token frequencies."""
import collections
import json
import os

import numpy as onp
import pytest

from chipbench_paths import BENCH
from chipbench import traffic as gen

BIG = 2**31 + 4242


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as fh:
        return json.load(fh)


def test_same_seed_same_requests():
    mix = dict(_mix("decode-saturated"), pool_repeats=1)
    assert gen.requests(mix, BIG, 50257) == gen.requests(mix, BIG, 50257)
    assert gen.requests(mix, BIG, 50257) != gen.requests(mix, BIG + 1, 50257)


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = dict(_mix("decode-saturated"), pool_repeats=2)
    a, b = gen.request_shapes(mix, 1), gen.request_shapes(mix, BIG)
    n = mix["request_pool"]
    assert len(a) == len(b) == 2 * n and a != b
    for lo in (0, n):   # each pass through the pool is the whole pool
        assert collections.Counter(a[lo:lo + n]) \
            == collections.Counter(b[lo:lo + n])


def test_decode_saturated_lengths_are_as_stated():
    mix = _mix("decode-saturated")
    shapes = gen.request_shapes(dict(mix, pool_repeats=1), 0)
    prompts = [p for p, _ in shapes]
    outs = [n for _, n in shapes]
    assert 33 <= min(prompts) and max(prompts) <= 512
    assert 16 <= min(outs) and max(outs) <= 96
    assert 140 < onp.mean(prompts) < 210      # log-uniform: mean about 175
    assert 50 < onp.mean(outs) < 62           # uniform: mean 56
    e = mix["engine"]
    assert max(prompts) <= e["max_prompt_len"]
    assert max(prompts) + max(outs) <= mix["check_pad_to"] <= e["max_len"]
    assert mix["clients"] == e["num_slots"]


@pytest.mark.parametrize("dist,lo,hi", [
    ("uniform", 3, 9), ("loguniform", 3, 900), ("fixed", 7, 7)])
def test_length_distributions_keep_their_ends(dist, lo, hi):
    v = gen._lengths(onp.random.default_rng(0),
                     {"dist": dist, "min": lo, "max": hi}, 4000)
    assert v.min() >= lo and v.max() <= hi
    if dist != "fixed":
        assert v.min() == lo and v.max() > 0.9 * hi


def test_zipf_tokens_are_skewed_and_spread_over_the_ids():
    toks = gen.zipf_tokens(gen.rng(BIG, 3), 1000, 1.0, (200, 100))
    assert toks.dtype == onp.int32 and toks.min() >= 0 and toks.max() < 1000
    counts = onp.bincount(toks.ravel(), minlength=1000)
    top = onp.sort(counts)[::-1]
    # rank 1 has probability 1/H(1000) = 0.134, rank 2 half of it
    assert 0.11 < top[0] / toks.size < 0.16
    assert 1.6 < top[0] / top[1] < 2.5
    assert onp.argmax(counts) != 0        # the permutation moved rank 1
    assert (counts > 0).sum() > 500


def test_token_rows_depend_on_the_seed_only():
    mix = _mix("train-1k")
    a = gen.token_rows(mix, BIG, 50257, 4, 1025)
    assert a.shape == (4, 1025)
    assert (a == gen.token_rows(mix, BIG, 50257, 4, 1025)).all()
    assert (a != gen.token_rows(mix, BIG + 1, 50257, 4, 1025)).any()
    assert 0 <= gen.seed31(BIG, 0) < 2**31
