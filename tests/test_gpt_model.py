"""GPT-style causal LM (gluon/model_zoo/gpt.py).

Reference pattern: the reference's word-LM example flow (train a few steps,
perplexity drops) + transformer op tests, applied to the decoder-only
family.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np, npx
from mxnet_tpu.gluon.model_zoo import gpt_tiny

RS = onp.random.RandomState(0)


def test_gpt_forward_and_causality():
    mx.random.seed(0)
    net = gpt_tiny(vocab_size=50, dropout=0.0)
    net.initialize()
    x = RS.randint(0, 50, size=(2, 16)).astype("int32")
    logits = net(np.array(x))
    assert logits.shape == (2, 16, 50)
    # flipping a future token must not change earlier positions
    x2 = x.copy()
    x2[:, 10] = (x2[:, 10] + 1) % 50
    l2 = net(np.array(x2))
    a, b = logits.asnumpy(), l2.asnumpy()
    assert onp.abs(a[:, :10] - b[:, :10]).max() == 0.0
    assert onp.abs(a[:, 10:] - b[:, 10:]).max() > 0.0


@pytest.mark.parametrize("hybridize", [False, True])
def test_gpt_trains_on_copy_task(hybridize):
    """Next-token loss on a deterministic cyclic sequence must fall fast."""
    mx.random.seed(1)
    vocab = 12
    net = gpt_tiny(vocab_size=vocab, dropout=0.0, num_layers=1, units=32,
                   num_heads=2)
    net.initialize()
    if hybridize:
        net.hybridize()
    tr = mx.gluon.Trainer(net.collect_params(), "adam",
                          {"learning_rate": 3e-3})
    seq = onp.tile(onp.arange(vocab), 3)[None, :24].astype("int32")
    tokens = np.array(seq)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    losses = []
    for _ in range(25):
        with mx.autograd.record():
            logits = net(inp)
            logp = npx.log_softmax(logits, axis=-1)
            nll = -npx.pick(logp, tgt, axis=-1).mean()
        nll.backward()
        tr.step(1)
        losses.append(float(nll.asnumpy()))
    assert losses[-1] < losses[0] * 0.5, losses[::6]


def test_gpt_generate_modes():
    mx.random.seed(2)
    net = gpt_tiny(vocab_size=20, dropout=0.0, num_layers=1, units=32,
                   num_heads=2)
    net.initialize()
    out = net.generate(np.array([1, 2, 3]), max_new_tokens=4)
    assert len(out) == 7
    assert all(0 <= int(t) < 20 for t in out)
    out_t = net.generate(np.array([1, 2, 3]), max_new_tokens=4,
                         temperature=1.0)
    assert len(out_t) == 7


def test_gpt_padding_mask_regression():
    """Pad tokens must be invisible: a right-padded prompt with
    valid_length produces the same logits (at valid positions) and the
    same greedy tokens as the unpadded prompt. The old window loop
    LEFT-padded with no mask, so pads leaked into attention.

    The two forwards are separately compiled programs at T=5 and T=12:
    the masked softmax weights are bit-equal, but XLA:CPU sums the P@V
    contraction over 12 keys (7 of them exact zeros) in another order
    than over 5, so the logits agree to an ulp, not bitwise. A leaked
    pad would move them by ~1e-2."""
    mx.random.seed(4)
    net = gpt_tiny(vocab_size=40, dropout=0.0, num_layers=2, units=32,
                   num_heads=4, max_length=64)
    net.initialize()
    x = RS.randint(1, 40, size=(1, 5)).astype("int32")
    plain = net(np.array(x)).asnumpy()
    padded = onp.zeros((1, 12), "int32")
    padded[0, :5] = x[0]
    masked = net(np.array(padded),
                 np.array(onp.asarray([5], "int32"))).asnumpy()
    assert onp.abs(masked[0, :5] - plain[0]).max() < 1e-6

    # the windowed loop right-pads+masks internally: greedy tokens must
    # match the cached path, which never pads at all
    prompt = [int(t) for t in x[0]]
    want = net.generate(prompt, max_new_tokens=6, temperature=0.0,
                        use_cache=True)
    got = net.generate(prompt, max_new_tokens=6, temperature=0.0,
                       use_cache=False, window=16)
    assert got == want


def test_gpt_generate_cache_routing_and_parity():
    mx.random.seed(5)
    net = gpt_tiny(vocab_size=30, dropout=0.0, num_layers=1, units=32,
                   num_heads=2, max_length=32)
    net.initialize()
    prompt = [3, 1, 4, 1, 5, 9]
    cached = net.generate(prompt, max_new_tokens=8, temperature=0.0)
    naive = net.generate(prompt, max_new_tokens=8, temperature=0.0,
                         use_cache=False)
    assert cached == naive and len(cached) == len(prompt) + 8
    # past max_length the auto route falls back to the rolling window...
    long_out = net.generate(prompt, max_new_tokens=40, temperature=0.0)
    assert len(long_out) == len(prompt) + 40
    # ...and forcing the cache raises instead of silently clipping
    with pytest.raises(mx.base.MXNetError, match="max_length"):
        net.generate(prompt, max_new_tokens=40, temperature=0.0,
                     use_cache=True)


def test_gpt_weight_tying():
    net = gpt_tiny(vocab_size=30, tie_weights=True)
    net.initialize()
    names = list(net.collect_params())
    assert not any("lm_head" in n for n in names)
    untied = gpt_tiny(vocab_size=30, tie_weights=False)
    untied.initialize()
    assert any("lm_head" in n for n in untied.collect_params())
