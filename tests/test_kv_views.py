"""The seam between a servable model and the decode engine (ISSUE 30): a
model has ONE forward pass, handed a cache view (``cache=``) it calls
``view.attend(layer, q, k, v)`` and ``view.positions(limit)``, and says what
a cache must hold (``cache_spec()``). Nothing else of it is known to
``serve/decode``: a model defined HERE is served unchanged."""
import ast
import pathlib

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np, npx, telemetry as tm
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.block import HybridBlock
from mxnet_tpu.gluon.model_zoo import gpt_tiny
from mxnet_tpu.serve.decode import DecodeEngine
from mxnet_tpu.serve.decode import cache as kv

VOCAB, MAX_LEN, HEADS, UNITS = 40, 64, 4, 32


class _Layer(HybridBlock):
    """RMS-normed block: separate q/k/v projections, a gated SiLU MLP."""

    def __init__(self):
        super().__init__()
        self.norm_1 = nn.RMSNorm(in_channels=UNITS)
        self.norm_2 = nn.RMSNorm(in_channels=UNITS)
        dense = lambda n, i=UNITS: nn.Dense(  # noqa: E731
            n, flatten=False, use_bias=False, in_units=i)
        self.q, self.k, self.v, self.o = (dense(UNITS) for _ in range(4))
        self.gate, self.up = dense(2 * UNITS), dense(2 * UNITS)
        self.down = dense(UNITS, 2 * UNITS)


class TinyLM(HybridBlock):
    """Not a GPTModel: RMSNorm, no biases, gated MLP, an UNTIED head."""

    def __init__(self, layers=3):
        super().__init__()
        self.max_length = MAX_LEN
        self.embed = nn.Embedding(VOCAB, UNITS)
        self.pos = nn.Embedding(MAX_LEN, UNITS)
        self.layers = nn.HybridSequential()
        for _ in range(layers):
            self.layers.add(_Layer())
        self.norm = nn.RMSNorm(in_channels=UNITS)
        self.head = nn.Dense(VOCAB, flatten=False, use_bias=False,
                             in_units=UNITS)

    def cache_spec(self):
        return {"layers": len(self.layers), "heads": HEADS,
                "head_dim": UNITS // HEADS, "dtype": "float32"}

    def forward(self, tokens, valid_length=None, cache=None):
        T = tokens.shape[1]
        pos = np.arange(T, dtype="int32").reshape(1, T) if cache is None \
            else cache.positions(self.max_length)
        x = self.embed(tokens) + self.pos(pos)
        mask = None if valid_length is None else (
            np.arange(T, dtype="int32").reshape(1, T)
            < valid_length.astype("int32").reshape(-1, 1)
        ).reshape(-1, 1, 1, T)
        for i, lay in enumerate(self.layers):
            h = lay.norm_1(x)
            q, k, v = lay.q(h), lay.k(h), lay.v(h)
            if cache is None:
                attn = npx.multihead_attention(q, k, v, mask=mask,
                                               num_heads=HEADS, causal=True)
            else:
                attn = cache.attend(i, q, k, v)
            x = x + lay.o(attn)
            h = lay.norm_2(x)
            x = x + lay.down(npx.activation(lay.gate(h), "silu") * lay.up(h))
        return self.head(self.norm(x))


@pytest.fixture(scope="module")
def tiny_lm():
    mx.random.seed(23)
    model = TinyLM()
    model.initialize(mx.initializer.Normal(0.3))
    return model


@pytest.fixture(scope="module")
def gpt():
    mx.random.seed(11)
    model = gpt_tiny(vocab_size=VOCAB, dropout=0.0, num_layers=2,
                     units=UNITS, num_heads=HEADS, max_length=MAX_LEN)
    model.initialize()
    return model


def _full_forward_greedy(model, prompt, n, window=32):
    """The model's own reference: the whole right-padded window again for
    every token."""
    toks = list(prompt)
    for _ in range(n):
        row = onp.zeros((1, window), "int32")
        row[0, :len(toks)] = toks
        logits = model(np.array(row),
                       np.array(onp.asarray([len(toks)], "int32")))
        toks.append(int(logits[0, len(toks) - 1].asnumpy().argmax()))
    return toks[len(prompt):]


def test_a_model_defined_here_is_served_unchanged(tiny_lm):
    """One forward and ``cache_spec``: that is all ``DecodeEngine`` needs,
    with the prefix cache and speculation on. The tokens are those of the
    model's own full-forward greedy loop; nothing compiles after warm-up."""
    assert not [n for n in dir(TinyLM) if n.endswith(("_paged", "_join"))
                or n.startswith(("forward_", "init_"))]
    rs = onp.random.RandomState(4)
    shared = rs.randint(1, VOCAB, 9).tolist()          # covers one page
    prompts = [shared + rs.randint(1, VOCAB, n).tolist() for n in (2, 5, 7)] \
        + [rs.randint(1, VOCAB, n).tolist() for n in (1, 6, 13)]
    want = [_full_forward_greedy(tiny_lm, p, 9) for p in prompts]
    assert len({tuple(w) for w in want}) > 1           # not one fixed point
    eng = DecodeEngine(tiny_lm, num_slots=2, max_len=MAX_LEN,
                       max_prompt_len=16, prefill_batch=2, page_tokens=8,
                       speculate_k=2, prefix_cache=True, cache_dir=False)
    was_on = tm.ON
    try:
        tm.enable()
        eng.warmup()
        c0 = tm.metrics()["jit.compiles"]
        assert int(c0) >= 1
        got = [eng.submit(p, max_new_tokens=9).result(timeout=300)
               for p in prompts]
        assert int(tm.metrics()["jit.compiles"] - c0) == 0
        stats = eng.stats()
    finally:
        eng.close()
        if not was_on:
            tm.disable()
    assert got == want
    assert stats["prefix_hit_tokens"] >= 16            # two joins of a page
    assert stats["speculate_k"] == 2


@pytest.mark.parametrize("which", ["gpt", "tiny_lm"])
def test_prefill_view_logits_are_the_plain_forwards(which, request):
    """``net(tokens, valid_length)`` and the same forward over the prefill
    view agree BITWISE at every valid position: one forward cannot drift
    from itself."""
    model = request.getfixturevalue(which)
    rs = onp.random.RandomState(6)
    tokens = np.array(rs.randint(1, VOCAB, (3, 16)).astype("int32"))
    valid = onp.array([12, 5, 16], "int32")
    plain = model(tokens, np.array(valid)).asnumpy()
    kp, vp = kv.empty_pools(model.cache_spec(), 6, 8)
    table = np.array(onp.array([[0, 1, 6], [2, 3, 6], [4, 5, 6]], "int32"))
    view = kv.PrefillView(tokens, np.array(valid), table, kp, vp)
    cached = model(tokens, cache=view).asnumpy()
    for row, n in enumerate(valid):
        onp.testing.assert_array_equal(cached[row, :n], plain[row, :n])
    kp2, _ = view.state()
    assert kp2.shape == kp.shape and float(abs(kp2.asnumpy()).sum()) > 0


def test_cached_generate_crosses_pages_like_the_uncached_loop():
    """``generate(use_cache=True)`` is the engine's single-request case
    over a private pool of 128-position pages: a request of 137 positions
    starts its second page mid-way."""
    mx.random.seed(5)
    model = gpt_tiny(vocab_size=VOCAB, dropout=0.0, num_layers=1,
                     units=UNITS, num_heads=2, max_length=160)
    model.initialize()
    prompt = onp.random.RandomState(8).randint(1, VOCAB, 121).tolist()
    cached = model.generate(prompt, max_new_tokens=16, temperature=0.0)
    plain = model.generate(prompt, max_new_tokens=16, temperature=0.0,
                           use_cache=False, window=160)
    assert cached == plain and len(cached) == 137


def test_serving_names_nothing_of_a_model_but_the_interface():
    """Source check: under ``mxnet_tpu/serve/`` a model is called, asked
    for ``cache_spec()``, ``max_length``, its parameters and (for tp) its
    partition rules. No forward pass by another name, no private."""
    allowed = {"cache_spec", "max_length", "collect_params",
               "tp_partition_rules"}
    banned = ("_qkv", "_post_attention", "forward_prefill",
              "forward_decode", "init_cache", "init_paged_cache")
    root = pathlib.Path(mx.__file__).parent / "serve"
    files = sorted(root.rglob("*.py"))
    assert len(files) >= 8
    for path in files:
        text = path.read_text()
        for name in banned:
            assert name not in text, f"{path}: {name}"
        for node in ast.walk(ast.parse(text)):
            if not isinstance(node, ast.Attribute):
                continue
            owner = node.value
            owner = owner.id if isinstance(owner, ast.Name) else \
                owner.attr if isinstance(owner, ast.Attribute) else None
            if owner in ("model", "_model"):
                assert node.attr in allowed, \
                    f"{path}:{node.lineno}: model.{node.attr}"
    zoo = (pathlib.Path(mx.__file__).parent / "gluon" / "model_zoo"
           / "gpt.py").read_text()
    assert zoo.count("    def forward(") == 2    # layer and model: one each
    assert "def forward_" not in zoo and "_paged" not in zoo


# -- two kinds of state: pages and a recurrent state a slot ----------------
class LeakyLM(HybridBlock):
    """Not in the zoo: one leaky integrator ``h_t = a h_(t-1) + x_t W`` (a
    state of UNITS numbers a sequence), then one attention layer. Its whole
    contract with serving is ``cache.recur`` beside ``cache.attend`` and a
    ``cache_spec()`` that states both."""

    def __init__(self):
        super().__init__()
        self.max_length = MAX_LEN
        self.embed = nn.Embedding(VOCAB, UNITS)
        dense = lambda n: nn.Dense(n, flatten=False, use_bias=False,  # noqa
                                   in_units=UNITS)
        self.inp, self.q, self.k, self.v, self.o = (dense(UNITS)
                                                    for _ in range(5))
        self.head = dense(VOCAB)

    def cache_spec(self):
        return {"layers": 1, "heads": HEADS, "head_dim": UNITS // HEADS,
                "dtype": "float32", "state": (((UNITS,), "float32"),),
                "state_layers": 1, "counters": ("steps",)}

    def forward(self, tokens, valid_length=None, cache=None):
        T = tokens.shape[1]
        x = self.embed(tokens)
        u = self.inp(x)

        def step(h, valid):
            if h is None:
                h = u[:, 0] * 0
            out = []
            for t in range(T):
                new = 0.8 * h + u[:, t]
                h = new if valid is None else np.where(
                    (valid.astype("int32") > t).reshape(-1, 1), new, h)
                out.append(h)
            return np.stack(out, axis=1), h

        if cache is None:
            y = step(None, valid_length)[0]
        else:
            y = cache.recur(0, step)
            cache.count("steps", np.sum(cache.token_mask().astype("int32")))
        x = x + np.tanh(y)
        q, k, v = self.q(x), self.k(x), self.v(x)
        if cache is None:
            mask = None if valid_length is None else (
                np.arange(T, dtype="int32").reshape(1, T)
                < valid_length.astype("int32").reshape(-1, 1)
            ).reshape(-1, 1, 1, T)
            attn = npx.multihead_attention(q, k, v, mask=mask,
                                           num_heads=HEADS, causal=True)
        else:
            attn = cache.attend(0, q, k, v)
        return self.head(x + self.o(attn))


@pytest.fixture(scope="module")
def leaky_lm():
    mx.random.seed(31)
    model = LeakyLM()
    model.initialize(mx.initializer.Normal(0.4))
    return model


def test_a_model_with_recurrent_state_defined_here_is_served(leaky_lm):
    """Seven requests on two slots: every slot has several tenants, each
    starts from an empty state, and the tokens are the full forward's."""
    rs = onp.random.RandomState(9)
    prompts = [rs.randint(1, VOCAB, n).tolist()
               for n in (3, 11, 6, 16, 1, 9, 13)]
    want = [_full_forward_greedy(leaky_lm, p, 8) for p in prompts]
    assert len({tuple(w) for w in want}) > 3
    eng = DecodeEngine(leaky_lm, num_slots=2, max_len=MAX_LEN,
                       max_prompt_len=16, prefill_batch=2, page_tokens=8,
                       speculate_k=1, prefix_cache=False, cache_dir=False)
    try:
        eng.warmup()
        streams = [eng.submit(p, max_new_tokens=8) for p in prompts]
        got = [s.result(timeout=300) for s in streams]
        stats = eng.stats()
    finally:
        eng.close()
    assert got == want
    assert stats["state_bytes"] == 2 * UNITS * 4
    # every real token but each request's last went through the layer once
    assert stats["steps"] == sum(map(len, prompts)) + 7 * 7


@pytest.mark.parametrize("which", ["tiny_lm", "leaky_lm"])
def test_a_plain_tick_stores_its_rows_through_the_paged_kernel(
        which, request, paged_kernel_interpreted):
    """K = 1 on pages of 128 positions: the tick's op takes the Pallas
    kernel (interpreted), which stores each slot's new row itself. More
    requests than slots, with and without recurrent state beside the
    pages: the tokens are the full forward's."""
    model = request.getfixturevalue(which)
    rs = onp.random.RandomState(6)
    prompts = [rs.randint(1, VOCAB, n).tolist() for n in (4, 13, 1, 16, 7)]
    want = [_full_forward_greedy(model, p, 8) for p in prompts]
    eng = DecodeEngine(model, num_slots=2, max_len=MAX_LEN,
                       max_prompt_len=16, prefill_batch=2,
                       page_tokens=128, speculate_k=1,
                       prefix_cache=False, cache_dir=False)
    try:
        streams = [eng.submit(p, max_new_tokens=8) for p in prompts]
        got = [s.result(timeout=300) for s in streams]
    finally:
        eng.close()
    assert paged_kernel_interpreted and all(paged_kernel_interpreted)
    assert got == want


def test_a_cache_names_its_operands_and_an_empty_state_adds_none(
        gpt, leaky_lm):
    """The donated operands of every program are what the cache names. A
    model of attention layers only has the pool pair and nothing else, at
    the places they always had; a recurrent model's state follows the pair
    and its counters come last, undonated; its prefill takes each row's
    slot."""
    from mxnet_tpu.serve.decode import DecodePrograms

    kw = dict(num_slots=2, max_len=32, max_prompt_len=8, prefill_batch=1,
              page_tokens=8, prefix_cache=False)
    plain = DecodePrograms(gpt, **dict(kw, prefix_cache=True))
    assert plain.state_shapes == [] and plain.counter_names == ()
    assert [plain._donate(f) for f in ("decode", "prefill", "prefill_ext")] \
        == [(3, 4), (3, 4), (4, 5)]
    assert not plain.recurrent
    assert len(kv.empty_state(gpt.cache_spec(), 2)) == 0
    leaky = DecodePrograms(leaky_lm, **kw)
    assert leaky.state_shapes == [((2, UNITS), "float32")]
    assert leaky.counter_names == ("steps",)
    assert leaky._donate("decode") == (3, 4, 5)
    assert leaky._donate("prefill") == (4, 5, 6)      # after the slots
    cache = kv.PagedKVCache(leaky.cache_shape, "float32", num_slots=2,
                            max_len=32, state=leaky.state_shapes,
                            counters=leaky.counter_names)
    k, v, state, counters = cache.operands()
    assert state.shape == (2, UNITS) and counters.shape == (1,)
    cache.rebind([v, k, state + 1, counters + 2, "an auxiliary output"])
    assert cache.k is v and float(cache.state[0][0, 0]) == 1.0
    assert int(cache.counters[0]) == 2
    assert cache.state_nbytes == 2 * UNITS * 4


def test_a_prefill_writes_its_rows_state_to_their_slots(leaky_lm):
    """``PrefillView.recur`` starts from zeros whatever the slot held,
    keeps the state as of ``valid_length``, and a row that names slot
    ``num_slots`` (a bucket's empty row) writes nothing."""
    spec = leaky_lm.cache_spec()
    rs = onp.random.RandomState(3)
    tokens = np.array(rs.randint(1, VOCAB, (3, 8)).astype("int32"))
    valid = np.array(onp.array([8, 3, 5], "int32"))
    kp, vp = kv.empty_pools(spec, 4, 8)
    state = np.array(onp.full((4, UNITS), 9.0, "float32"))
    table = np.array(onp.array([[0, 4], [1, 4], [2, 4]], "int32"))
    view = kv.PrefillView(
        tokens, valid, table, kp, vp, state, np.zeros((1,), dtype="int32"),
        slots=np.array(onp.array([2, 0, 4], "int32")),
        **kv.view_layout(spec))
    leaky_lm(tokens, cache=view)
    new, counters = (a.asnumpy() for a in view.state()[2:])
    assert (new[1] == 9.0).all() and (new[3] == 9.0).all()   # untouched
    assert int(counters[0]) == 8 + 3 + 5
    for slot, row, n in ((2, 0, 8), (0, 1, 3)):
        alone = kv.PrefillView(
            tokens[row:row + 1, :n], np.array(onp.array([n], "int32")),
            table[row:row + 1], kp, vp, state * 0,
            np.zeros((1,), dtype="int32"),
            slots=np.zeros((1,), dtype="int32"), **kv.view_layout(spec))
        leaky_lm(tokens[row:row + 1, :n], cache=alone)
        onp.testing.assert_allclose(new[slot],
                                    alone.state()[2].asnumpy()[0],
                                    rtol=1e-5, atol=1e-6)
