"""A.X-K1 at a tiny size on the CPU: the zoo model against the plain reference
(``benchmark/chip/chipbench/reference_axk1.py``), plain and through the cache
views (prefill expanded, ticks absorbed) over a LATENT cache: one pool of one
headless row a position and no V pool; YaRN's frequencies, rotary positions a
row, the sigmoid router, the blocked experts kernel and the absorbed decode
kernel in interpret mode, the shares of an expert-parallel layer, and what
latent rows refuse by name."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import model_zoo
from mxnet_tpu.ops import moe
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.serve import DecodeEngine
from mxnet_tpu.serve.decode import DecodePrograms
from mxnet_tpu.serve.decode import cache as kv

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "benchmark", "chip"))
from chipbench import reference_axk1 as ref  # noqa: E402

zoo = sys.modules["mxnet_tpu.gluon.model_zoo.axk1"]
HELD = (4, 12)


def _tokens(seed, shape, vocab=96):
    return onp.random.RandomState(seed).randint(1, vocab, shape) \
        .astype("int32")


def _weights(net):
    return {n: p.data()._data for n, p in net.collect_params().items()}


def _reference(net, toks, held=HELD, **kw):
    W = _weights(net)
    return ref.forward(W["embed.weight"], W["head.weight"],
                       W["norm_f.weight"], ref.by_layer(W), net.config, toks,
                       experts_held=held, **kw)


def _spread_error(got, want):
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32) - want))
                 / jnp.std(want))


@pytest.fixture(scope="module")
def net():
    mx.random.seed(7)
    net = model_zoo.axk1_tiny(experts_held=HELD)
    net.initialize()
    # larger matrices: the logits then vary from token to token, and a
    # lost term shows
    for name, p in net.collect_params().items():
        if p.data().ndim >= 2 and "embed" not in name:
            p.set_data(p.data() * 4.0)
    return net


# -- YaRN, rope, the router ---------------------------------------------------
def test_yarn_blends_the_frequencies_between_dimension_10_and_23():
    scaling = zoo.AX_K1["rope_scaling"]
    inv = zoo.yarn_inv_freq(64, 10000, scaling)
    f = [10000 ** (-2 * i / 64) for i in range(32)]

    def corr(b):
        return 64 * math.log(4096 / (2 * math.pi * b)) / (2 * math.log(10000))

    assert (math.floor(corr(32)), math.ceil(corr(1))) == (10, 23)
    for i in range(32):
        ramp = min(max((i - 10) / 13, 0.0), 1.0)
        assert inv[i] == pytest.approx(f[i] * (1 - ramp) + f[i] / 32 * ramp,
                                       rel=1e-12)
    assert inv[:11] == f[:11] and inv[23:] == [v / 32 for v in f[23:]]
    assert inv == pytest.approx(ref.yarn_inv_freq(64, 10000, scaling),
                                rel=1e-12)
    m, ratio = zoo.yarn_mscale(scaling)
    assert m == pytest.approx(1.346574, abs=1e-6) and ratio == 1.0
    assert 192 ** -0.5 * m * m == pytest.approx(0.130861, abs=1e-6)
    assert ref.softmax_scale(zoo.AX_K1) == pytest.approx(0.130861, abs=1e-6)


def _old_rope(x, rot, theta):
    """``npx.rope`` as it stood before positions a row (PR 35)."""
    half = rot // 2
    inv = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(ang)[None, :, None].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def test_rope_without_positions_gives_the_numbers_it_gave():
    """The Qwen model's calls (rotary_dim, theta, float32)."""
    x = jnp.asarray(onp.random.RandomState(0).randn(2, 24, 3, 16), "float32")
    got = mx.npx.rope(mx.np.array(x), rotary_dim=8, theta=1e7)._data
    # both compiled: XLA contracts a*b - c*d the same way in each
    assert jnp.array_equal(
        got, jax.jit(_old_rope, static_argnums=(1, 2))(x, 8, 1e7))


def test_rope_takes_positions_a_row_and_given_frequencies():
    x = jnp.asarray(onp.random.RandomState(1).randn(3, 5, 2, 8), "float32")
    inv = [1.0, 0.3, 0.05, 0.002]
    pos = onp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11],
                       [100, 3, 3, 0, 2000]], "int32")
    got = mx.npx.rope(mx.np.array(x), positions=mx.np.array(pos),
                      inv_freq=inv)._data
    ang = pos[:, :, None].astype("float64") * onp.asarray(inv)
    x1, x2 = onp.asarray(x[..., :4], "float64"), \
        onp.asarray(x[..., 4:], "float64")
    cos, sin = onp.cos(ang)[:, :, None], onp.sin(ang)[:, :, None]
    want = onp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    assert onp.abs(onp.asarray(got) - want).max() < 2e-4   # float32 angles
    # one row of positions serves every row of the batch; an offset is the
    # same positions
    row = mx.npx.rope(mx.np.array(x), inv_freq=inv, positions=mx.np.array(
        onp.arange(5, dtype="int32")[None] + 7))._data
    assert jnp.allclose(
        row, mx.npx.rope(mx.np.array(x), inv_freq=inv, offset=7)._data,
        atol=1e-6)
    assert jnp.array_equal(row[1], got[1])
    # bfloat16 data: angles and rotation in float32, rounded once
    xb = x.astype(jnp.bfloat16)
    gb = mx.npx.rope(mx.np.array(xb), positions=mx.np.array(pos),
                     inv_freq=inv)._data
    assert gb.dtype == jnp.bfloat16
    assert onp.abs(onp.asarray(gb.astype(jnp.float32)) - want).max() < 0.05
    with pytest.raises(MXNetError, match="inverse frequencies"):
        mx.npx.rope(mx.np.array(x), inv_freq=[1.0, 0.5])


def test_the_sigmoid_router_against_a_hand_count():
    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]], jnp.float32)
    w = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [-1.0, -1.0], [2.0, 0.5]],
                    jnp.float32)
    vals, idx, counts = get_op("moe_router").fn(
        top_k=2, score="sigmoid", scaling=2.5)(x, w)
    sig = lambda v: 1 / (1 + math.exp(-v))  # noqa: E731
    # token 0: logits 1, 0, -1, 2 -> experts 3, 0; token 1: 0, 2, -2, 1
    assert onp.asarray(idx).tolist() == [[3, 0], [1, 3]]
    for row, (a, b) in zip(onp.asarray(vals), ((2, 1), (2, 1))):
        total = sig(a) + sig(b) + 1e-20
        assert row == pytest.approx([2.5 * sig(a) / total,
                                     2.5 * sig(b) / total], rel=1e-6)
    assert onp.asarray(counts).tolist() == [1, 1, 0, 2]
    with pytest.raises(MXNetError, match="neither"):
        get_op("moe_router").fn(top_k=1, score="tanh")(x, w)


def test_the_softmax_router_is_unchanged_bit_for_bit():
    rng = onp.random.RandomState(2)
    x = jnp.asarray(rng.randn(40, 16), jnp.float32)
    w = jnp.asarray(rng.randn(12, 16), jnp.float32)
    vals, idx, counts = get_op("moe_router").fn(top_k=3)(x, w)
    logits = jnp.matmul(x, w.T, precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    want, want_i = jax.lax.top_k(p, 3)
    want = want / jnp.sum(want, axis=-1, keepdims=True)
    assert jnp.array_equal(vals, want) and jnp.array_equal(idx, want_i)
    assert float(counts.sum()) == 120.0


# -- the model against the reference -----------------------------------------
@pytest.mark.parametrize("hybridize", [False, True],
                         ids=["eager", "hybridized"])
def test_whole_net_matches_the_reference(net, hybridize):
    toks = _tokens(3, (2, 40))
    if hybridize:
        net.hybridize()
    try:
        got = net(mx.np.array(toks))._data
    finally:
        net.hybridize(False)
    out = _reference(net, toks)
    assert _spread_error(got, out["logits"]) < 1e-4
    assert len(out["chosen"]) == 2 and len(out["rows"]) == 3
    assert out["rows"][0].shape == (2, 40, 24)
    assert float(jnp.std(out["logits"])) > 0.05


def test_the_zoo_builds_the_published_sizes_by_default():
    """Shapes only (nothing is initialised): the issue's table by part."""
    net = model_zoo.axk1(num_hidden_layers=2, vocab_size=20480,
                         experts_held=(0, 12))
    shapes = {n: p.shape for n, p in net.collect_params().items()}
    count = lambda prefix: sum(  # noqa: E731
        int(onp.prod(s)) for n, s in shapes.items() if n.startswith(prefix))
    assert count("layers.0.attn.") == 101_124_096      # matrices + norms
    assert count("layers.0.") == 101_124_096 + 3 * 7168 * 18432 + 2 * 7168
    assert shapes["layers.1.moe.gate_up"] == (12, 7168, 4096)
    assert shapes["layers.1.moe.down"] == (12, 2048, 7168)
    assert shapes["layers.1.moe.router.weight"] == (192, 7168)
    outside = count("layers.1.") - 12 * 3 * 7168 * 2048 - 192   # less counts
    assert outside == 101_124_096 + 44_040_192 + 1_376_256 + 14_336
    assert count("embed.") + count("head.") + count("norm_f.") \
        == 2 * 20480 * 7168 + 7168
    spec = net.cache_spec()
    assert spec["latent"] == (576, 512) and "heads" not in spec
    assert net.layers[0].attn.scale == pytest.approx(0.130861, abs=1e-6)
    # one 576-wide bfloat16 row a token and layer: 1152 bytes, one pool
    shape = kv.pool_shape(dict(spec, dtype="bfloat16"), 1280, 128)
    assert shape == (1280, 2, 1, 576, 128) and kv.pool_count(spec) == 1
    assert int(onp.prod(shape)) * 2 // (1280 * 128 * 2) == 1152


def test_a_padded_prompt_equals_the_prompt_alone(net):
    toks = _tokens(5, (1, 32))
    valid = mx.np.array(onp.asarray([19], "int32"))
    padded = net(mx.np.array(toks), valid_length=valid)._data[0, :19]
    alone = net(mx.np.array(toks[:, :19]))._data[0]
    assert _spread_error(padded, alone) < 1e-5


def _views(net, row, spans, P=8, bucket=32):
    """Prefill a slot (right-padded to ``bucket``), then ticks for all slots
    together: logits at prefix - 1 .. prefix + ticks - 1, and the pool."""
    spec = net.cache_spec()
    layout = kv.view_layout(spec)
    S = len(spans)
    W = max(-(-(p + k) // P) for p, k in spans)
    operands = kv.empty_pools(spec, S * W, P) + tuple(kv.empty_state(spec, S))
    assert len(kv.empty_pools(spec, S * W, P)) == 1
    table = onp.full((S, W + 1), S * W, "int32")
    table[:, :W] = onp.arange(S * W).reshape(S, W)
    logits = [[] for _ in spans]
    for s, (p, _) in enumerate(spans):
        tokens = onp.zeros((1, bucket), "int32")
        tokens[0, :p] = row[:p]
        view = kv.PrefillView(
            mx.np.array(tokens), mx.np.array(onp.asarray([p], "int32")),
            mx.np.array(table[s:s + 1]), *operands,
            slots=mx.np.array(onp.asarray([s], "int32")), **layout)
        logits[s].append(net(mx.np.array(tokens), cache=view)[0, p - 1]._data)
        operands = view.state()
    for k in range(spans[0][1]):
        at = onp.asarray([p + k for p, _ in spans], "int32")
        view = kv.TickView(mx.np.array(row[at].reshape(S, 1)),
                           mx.np.array(at), mx.np.array(table), *operands,
                           **layout)
        out = net(mx.np.array(row[at].reshape(S, 1)), cache=view)
        for s in range(S):
            logits[s].append(out[s, 0]._data)
        operands = view.state()
    return jnp.stack([jnp.stack(l) for l in logits]), operands, table


def test_prefill_then_ticks_equal_the_reference_at_every_position(net):
    """Two slots at different lengths, each prefix right-padded in its
    bucket: expanded attention in the prefill, absorbed in the ticks, the
    rotary position of each slot its own; logits against the reference's
    full forward, and the latent rows the cache is left holding against the
    reference's ``[c | k^r]``."""
    row = _tokens(11, (40,))
    spans = [(27, 6), (11, 6)]
    logits, operands, table = _views(net, row, spans)
    out = _reference(net, row[None])
    for s, (p, k) in enumerate(spans):
        assert _spread_error(logits[s], out["logits"][0, p - 1:p + k]) < 1e-4
    pool = operands[0]._data
    assert pool.shape[2:] == (1, 24, 8) and len(operands) == 2   # + counters
    W = table.shape[1] - 1
    for layer in range(3):
        held = pool[table[:, :W].reshape(-1), layer, 0].reshape(2, W, 24, 8)
        held = jnp.swapaxes(held, 2, 3).reshape(2, W * 8, 24)
        for s, (p, k) in enumerate(spans):
            want = out["rows"][layer][0, :p + k]
            assert float(jnp.max(jnp.abs(held[s, :p + k] - want))) \
                < 1e-4 * float(jnp.max(jnp.abs(want)))


def test_absorbed_equals_expanded_in_float32(net):
    """One attention layer: the tick's absorbed form over the rows a prefill
    stored gives what the expanded form gives at the same position."""
    attn = net.layers[0].attn                        # pool layer 0
    x = mx.np.array(onp.random.RandomState(4).randn(1, 16, 32)
                    .astype("float32"))
    expanded = attn(x)._data                         # plain, causal
    spec = dict(net.cache_spec(), counters=(), layers=1)
    layout = kv.view_layout(spec)
    pools = kv.empty_pools(spec, 2, 8)
    table = mx.np.array(onp.asarray([[0, 1, 2]], "int32"))
    toks = mx.np.zeros((1, 16), dtype="int32")
    view = kv.PrefillView(toks, mx.np.array(onp.asarray([15], "int32")),
                          table, *pools, **layout)
    pos = view.positions(1024)
    first = attn(x, cache=view, positions=pos)._data
    assert _spread_error(first[0, :15], expanded[0, :15]) < 1e-5
    tick = kv.TickView(mx.np.zeros((1, 1), dtype="int32"),
                       mx.np.array(onp.asarray([15], "int32")), table,
                       *view.state(), **layout)
    absorbed = attn(x[:, 15:16], cache=tick,
                    positions=tick.positions(1024))._data
    assert _spread_error(absorbed[0, 0], expanded[0, 15]) < 1e-5


def test_generate_equals_the_full_forward_greedy_loop(net):
    prompt = [int(t) for t in _tokens(6, (9,))]
    seq = list(prompt)
    for _ in range(8):
        logits = net(mx.np.array(onp.asarray([seq], "int32"))).asnumpy()
        seq.append(int(logits[0, -1].argmax()))
    assert [int(t) for t in net.generate(prompt, 8)] == seq
    assert len(set(seq[9:])) > 2


# -- through the engine -------------------------------------------------------
@pytest.fixture(scope="module")
def served(net):
    rng = onp.random.RandomState(8)
    prompts = [[int(t) for t in rng.randint(1, 96, rng.randint(3, 28))]
               for _ in range(7)]
    wants = [int(rng.randint(3, 9)) for _ in prompts]
    alone = [[int(t) for t in net.generate(p, n)][len(p):]
             for p, n in zip(prompts, wants)]
    eng = DecodeEngine(net, num_slots=3, max_len=48, max_prompt_len=32,
                       prefill_batch=1, page_tokens=8, prefix_cache=False,
                       speculate_k=1)
    try:
        eng.warmup()
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, wants)]
        got = [[int(t) for t in s.result(timeout=300)] for s in streams]
        stats = eng.stats()
        cache = eng._cache
        donate = eng.programs._donate("decode")
    finally:
        eng.close()
    return {"got": got, "alone": alone, "stats": stats, "cache": cache,
            "donate": donate, "prompts": prompts, "wants": wants}


def test_every_request_gets_the_tokens_it_gets_alone(served):
    assert served["got"] == served["alone"]
    st = served["stats"]
    assert st["completed"] == 7 and st["shed"] == st["evicted"] == 0


def test_the_cache_is_one_latent_pool_and_stats_say_its_bytes(served, net):
    cache, st = served["cache"], served["stats"]
    assert len(cache.pools) == 1 and cache.pools[0].shape == (18, 3, 1, 24, 8)
    with pytest.raises(IndexError):
        cache.v
    # float32 here: 24 x 4 bytes a token and layer, three layers
    assert st["cache_bytes"] == cache.nbytes == 18 * 8 * 3 * 24 * 4
    assert st["cache_bytes"] // (st["kv_pages"] * st["page_tokens"]) \
        == 3 * 24 * 4
    assert st["state_bytes"] == 0
    # donated: the tick's tokens, positions, table, then ONE pool
    assert served["donate"] == (3,)
    assert len(cache.operands()) == 2          # the pool, the counters
    tokens = st["prompt_tokens"] + sum(served["wants"]) - 7
    assert 0 < st["moe_pairs_here"] <= 2 * 4 * tokens
    assert 0 < st["moe_experts_touched"] <= st["ticks"] * 2 * 8
    from mxnet_tpu.serve.decode import engine

    last = engine.stats_log()[-1]
    assert last["cache_bytes"] == st["cache_bytes"]
    assert last["kv_pages"] == st["kv_pages"]


@pytest.mark.parametrize("what, kwargs", [
    ("prefix_cache=True", {"prefix_cache": True}),
    ("speculate_k=2", {"prefix_cache": False, "speculate_k": 2}),
    ("tp=2", {"prefix_cache": False, "tp": 2}),
])
def test_latent_rows_refuse_by_name_what_they_cannot_do_yet(net, what,
                                                            kwargs):
    with pytest.raises(MXNetError) as e:
        DecodePrograms(net, num_slots=2, max_len=32, max_prompt_len=16,
                       prefill_batch=1, page_tokens=8, **kwargs)
    assert "latent" in str(e.value) and what in str(e.value)


def test_a_join_view_and_an_export_refuse_a_latent_cache(net, tmp_path):
    spec = net.cache_spec()
    pools = kv.empty_pools(spec, 2, 8) + tuple(kv.empty_state(spec, 1))
    with pytest.raises(MXNetError, match="prefix join"):
        kv.JoinView(mx.np.zeros((1, 8), dtype="int32"),
                    mx.np.ones((1,), dtype="int32"),
                    mx.np.zeros((1,), dtype="int32"),
                    mx.np.zeros((1, 3), dtype="int32"), *pools,
                    **kv.view_layout(spec))
    progs = DecodePrograms(net, num_slots=2, max_len=16, max_prompt_len=8,
                           prefill_batch=1, page_tokens=8,
                           prefix_cache=False)
    with pytest.raises(MXNetError, match="latent cache"):
        progs.export(str(tmp_path / "x"))


# -- the shares of a layer ----------------------------------------------------
def test_the_16_shares_of_a_layer_add_up_to_the_whole():
    """192 experts, top-8, sigmoid, scaling 2.5: sixteen layers that hold
    twelve experts each, with the shared expert (which every chip computes
    alike) counted once, sum to the uncut reference's layer."""
    mx.random.seed(1)
    D, F, E, k = 16, 8, 192, 8
    kw = dict(score="sigmoid", scaling=2.5, norm_topk=True)
    granite = sys.modules["mxnet_tpu.gluon.model_zoo.granite_hybrid"]
    whole = granite.RoutedPlusShared(D, F, E, k, F, **kw)
    whole.initialize()
    for p in whole.collect_params().values():
        if p.data().ndim >= 2:
            p.set_data(p.data() * 10.0)
    x = onp.random.RandomState(1).randn(1, 40, D).astype("float32")
    full = whole.collect_params()
    cfg = dict(zoo.AX_K1, num_experts_per_tok=k)
    w = {n: p.data()._data for n, p in full.items()}
    with jax.default_matmul_precision("highest"):
        want, margin, _ = ref.routed_plus_shared(jnp.asarray(x), w, cfg,
                                                 (0, E))
    assert float(margin) == 1.0
    assert _spread_error(whole(mx.np.array(x))._data, want) < 1e-4
    total, shared = 0.0, None
    for lo in range(0, E, 12):
        share = granite.RoutedPlusShared(D, F, E, k, F,
                                         experts_held=(lo, lo + 12), **kw)
        share.initialize()
        for name, p in share.collect_params().items():
            value = full[name].data()._data
            p.set_data(mx.np.array(
                value[lo:lo + 12] if name in ("gate_up", "down") else value))
        xs = mx.np.array(x[0])
        y = share(mx.np.array(x))._data[0]
        routed = share.experts(xs, *share.router(xs))._data
        shared = y - routed
        total = total + routed
    assert _spread_error(total + shared, want[0]) < 1e-4
    # the weights of a token's eight experts sum to 2.5
    weights, _ = whole.router(mx.np.array(x[0]))
    assert onp.allclose(weights.asnumpy().sum(-1), 2.5, atol=1e-5)


# -- the kernels, interpreted -------------------------------------------------
def _latent_case(seed, k=1, h=4, r=192, v=128, p=128, w=3, dtype="float32",
                 lengths=(300, 130, 5)):
    """Slot i's live pages are its own block of ``w``, slot 0's out of
    order; page ``len(lengths) * w`` belongs to no slot."""
    rng = onp.random.RandomState(seed)
    s = len(lengths)
    pages = s * w
    pool = jnp.asarray(rng.randn(pages + 1, 2, 1, r, p) * 0.5, dtype)
    q = jnp.asarray(rng.randn(s, k, h, r) * 0.5, dtype)
    table = onp.full((s, w + 1), pages + 1, "int32")
    for i, n in enumerate(lengths):
        live = -(-(n + k) // p)
        table[i, :live] = rng.permutation(w)[:live] if i == 0 \
            else onp.arange(i * w, i * w + live)
    return q, pool, jnp.asarray(table), jnp.asarray(lengths, jnp.int32), v


ABSORBED = {
    # id: keywords of _latent_case, and whether every column past a slot's
    # last live page maps the spare page, full of NaN
    "float32-1": (dict(dtype="float32", k=1), False),
    "bfloat16-1": (dict(dtype="bfloat16", k=1), False),
    "float32-2": (dict(dtype="float32", k=2), False),
    # length + K ends on a page: no page holds a position past it
    "ends_on_a_page": (dict(k=1, lengths=(255, 127, 383)), False),
    # slot 0 fills its row but its last position; slot 1 fills all of it
    "every_column_live": (dict(k=1, lengths=(382, 383, 20)), False),
    # queries at 127 | 128 and 255 | 256: the newest opens a page of one row
    "k2_opens_a_page": (dict(k=2, lengths=(127, 255, 30)), False),
    "nan_past_the_length": (dict(k=1, lengths=(300, 130, 5)), True),
    "bfloat16_cell_widths": (dict(dtype="bfloat16", k=1, h=64, r=576,
                                  v=512, w=6, lengths=(700, 130, 5)), False),
    # 3, 1, 2 and 1 groups of four pages: the copies alternate halves of
    # the buffer across groups and slots
    "groups_across_slots": (dict(k=1, w=9, lengths=(1100, 130, 600, 5)),
                            True),
    "k2_groups_across_slots": (dict(k=2, w=9, lengths=(1023, 0, 510, 1150)),
                               False),
}


@pytest.mark.parametrize("case", list(ABSORBED))
def test_the_absorbed_decode_kernel_equals_the_plain_body(case, monkeypatch):
    """``mxtpu_mla_decode`` interpreted, against the gather + softmax of the
    same numbers and against the definition, every slot and query: pages
    out of order, a slot inside its first page, an unmapped tail, NaN past
    each slot's length in its last live page."""
    kw, nan_tail = ABSORBED[case]
    k, dtype = kw["k"], kw.get("dtype", "float32")
    q, pool, table, pos, v = _latent_case(0, **kw)
    s, _, h, _ = q.shape
    p, spare = pool.shape[-1], pool.shape[0] - 1
    live = [-(-(int(n) + k) // p) for n in pos]
    for i, n in enumerate(pos):
        if (int(n) + k) % p:
            pool = pool.at[int(table[i, live[i] - 1]), :, :, :,
                           (int(n) + k) % p:].set(jnp.nan)
    if nan_tail:
        pool = pool.at[spare].set(jnp.nan)
        table = jnp.where(jnp.arange(table.shape[1])[None, :]
                          < jnp.asarray(live)[:, None], table, spare)
        table = table.at[:, -1].set(spare + 1)
    want = pk._mla_decode_reference(q, pool, jnp.int32(1), table, pos, v, 0.2)
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    got = pk.mla_decode_attention(q, pool, 1, table, pos, v, 0.2)
    assert got.shape == (s, k, h, v) and got.dtype == q.dtype
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    assert not bool(jnp.isnan(got.astype(jnp.float32)).any())
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) < tol
    # against the definition: query kk of slot i sees positions <= n + kk
    for i, n in enumerate(pos):
        rows = jnp.concatenate([pool[int(t), 1, 0].T
                                for t in table[i, :live[i]]])
        for kk in range(k):
            seen = rows[:int(n) + kk + 1].astype(jnp.float32)
            sc = (q[i, kk].astype(jnp.float32) @ seen.T) * 0.2
            direct = jax.nn.softmax(sc, axis=-1) @ seen[:, :v]
            assert float(jnp.max(jnp.abs(got[i, kk].astype(jnp.float32)
                                         - direct))) < 5 * tol


def test_a_slot_with_no_page_attends_nothing(monkeypatch):
    q, pool, table, pos, v = _latent_case(1)
    table = table.at[1].set(pool.shape[0])
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    got = pk.mla_decode_attention(q, pool, 0, table, pos, v, 0.2)
    assert float(jnp.abs(got[1]).max()) == 0.0
    assert float(jnp.abs(got[0]).max()) > 0.0


BLOCKED = {
    # name: (rows, dtype, held, top_k, tile, width of an expert)
    "f32_tick": (32, jnp.float32, (4, 12), 4, 128, 256),
    "bf16_tick": (32, jnp.bfloat16, (4, 12), 4, 128, 256),
    "bf16_several_tiles_an_expert": (256, jnp.bfloat16, (0, 4), 4, 32, 384),
    "f32_no_choice_held": (48, jnp.float32, (13, 16), 2, 128, 128),
}


@pytest.mark.parametrize("case", list(BLOCKED))
def test_the_blocked_experts_kernel_equals_the_loop(case, monkeypatch):
    """``mxtpu_experts_swiglu_blocked`` interpreted (an expert in blocks of
    128 columns of its width, the rule steered to it) against the loop."""
    n, dtype, (lo, hi), top_k, tile, F = BLOCKED[case]
    D, E = 128, 16
    rng = onp.random.RandomState(3)
    x = jnp.asarray(rng.randn(n, D), dtype)
    router = jnp.asarray(rng.randn(E, D) * 0.2, jnp.float32)
    gate_up = jnp.asarray(rng.randn(E, D, 2 * F) * 0.1, dtype)[lo:hi]
    down = jnp.asarray(rng.randn(E, F, D) * 0.1, dtype)[lo:hi]
    w, e, _ = get_op("moe_router")._make_fn(
        top_k=top_k, score="sigmoid", scaling=2.5)(x, router)
    op = get_op("routed_experts")._make_fn(experts_held=(lo, hi), tile=tile)
    with monkeypatch.context() as m:
        m.setattr(pk, "_use_pallas", lambda: False)
        loop = op(x, w, e, gate_up, down)
    calls = []
    blocked = pk.grouped_experts_blocked

    def spy(*args):
        calls.append(args[-1])
        return blocked(*args)

    with monkeypatch.context() as m:
        m.setenv("MXTPU_PALLAS_INTERPRET", "1")
        m.setattr(pk, "experts_kernel_blocks", lambda *a: 128)
        m.setattr(pk, "grouped_experts_blocked", spy)
        got = op(x, w, e, gate_up, down)
    assert calls == [128] and got.dtype == x.dtype
    f32 = lambda a: onp.asarray(a.astype(jnp.float32))  # noqa: E731
    scale = max(onp.abs(f32(loop)).max(), 1e-6)
    assert onp.abs(f32(got) - f32(loop)).max() <= 2e-2 * scale
    here = (onp.asarray(e) >= lo) & (onp.asarray(e) < hi)
    assert (f32(got)[~here.any(axis=1)] == 0).all()
    if "no_choice" not in case:
        assert here.any() and onp.abs(f32(got)).max() > 0


@pytest.mark.parametrize("case, want", [
    ("granite_tick", 0), ("granite_prefill_1024", 0),
    ("axk1_tick", 512), ("axk1_prefill_2048", 512),
    ("qwen_4096_float32_rows", None), ("lanes_not_whole", None)])
def test_one_rule_says_which_experts_kernel(case, want, monkeypatch):
    """Whole where an expert fits twice beside the sums (Granite's 9 MB: the
    program it had), in blocks of 512 where an EXPERT does not (A.X-K1's 88
    MB), the loop where only the sums are in the way (Qwen's training step:
    where it was) or the lanes are not whole."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    bf16, f32 = jnp.bfloat16, jnp.float32
    n, tm, d, f, dt = {
        "granite_tick": (32, 32, 4096, 768, bf16),
        "granite_prefill_1024": (1024, 128, 4096, 768, bf16),
        "axk1_tick": (64, 64, 7168, 2048, bf16),
        "axk1_prefill_2048": (2048, 128, 7168, 2048, bf16),
        "qwen_4096_float32_rows": (4096, 128, 2048, 512, f32),
        "lanes_not_whole": (32, 32, 96, 128, f32),
    }[case]
    assert pk.experts_kernel_blocks(n, tm, d, f, dt, dt) == want
    assert pk.experts_kernel_serves(n, tm, d, f, dt, dt) is (want == 0)
    monkeypatch.delenv("MXTPU_PALLAS_INTERPRET")
    assert pk.experts_kernel_blocks(n, tm, d, f, dt, dt) is None   # the CPU


def test_a_bfloat16_net_serves_through_the_engine():
    mx.random.seed(5)
    net = model_zoo.axk1_tiny(experts_held=(0, 8), dtype="bfloat16")
    for p in net.collect_params().values():
        p.grad_req = "null"
    net.initialize()
    assert net.collect_params()["layers.1.attn.k_up.weight"].data() \
        ._data.dtype == jnp.bfloat16
    eng = DecodeEngine(net, num_slots=2, max_len=32, max_prompt_len=16,
                       prefill_batch=1, page_tokens=8, prefix_cache=False,
                       speculate_k=1)
    try:
        out = eng.submit([3, 9, 27, 81], max_new_tokens=5).result(timeout=300)
        st = eng.stats()
    finally:
        eng.close()
    assert len(out) == 5 and eng._cache.pools[0].dtype == jnp.bfloat16
    assert st["cache_bytes"] // (st["kv_pages"] * st["page_tokens"]) \
        == 3 * 24 * 2
