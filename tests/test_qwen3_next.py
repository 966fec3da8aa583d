"""Qwen3-Next at a small size on the CPU against the plain reference
(``benchmark/chip/chipbench/reference_qwen3_next.py``): every layer kind and
the whole net, the chunked delta rule against the recurrence, gradients
through ``compile_step`` against ``jax.grad`` of the reference's loss, a
skewed router, and the shares of a routed layer adding up to the whole."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry, train_step
from mxnet_tpu.gluon import model_zoo
from mxnet_tpu.ops.registry import get_op

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from chipbench import reference, reference_qwen3_next as ref  # noqa: E402

# ``model_zoo.qwen3_next`` is the factory; the module of that name holds
# the blocks
zoo = sys.modules["mxnet_tpu.gluon.model_zoo.qwen3_next"]

HELD = (4, 12)


def _tokens(seed, shape, vocab=96):
    return onp.random.RandomState(seed).randint(0, vocab, shape).astype(
        "int32")


def _net(seed=3, **kw):
    mx.random.seed(seed)
    net = model_zoo.qwen3_next_tiny(**kw)
    net.initialize()
    # the published initialisation (A = U(1, 16)) forgets the state within a
    # position or two; a slow decay makes the carried state, and the
    # gradient that reaches A_log and dt_bias through it, count
    rng = onp.random.RandomState(seed)
    for name, p in net.collect_params().items():
        if name.endswith("A_log"):
            p.set_data(mx.np.array(onp.log(rng.uniform(
                0.02, 0.3, p.shape)).astype("float32")))
    return net


def _spread_error(got, want):
    """Largest difference as a share of the reference's spread."""
    return float(jnp.max(jnp.abs(got - want)) / jnp.std(want))


@pytest.fixture(scope="module")
def net():
    return _net(experts_held=HELD)


# -- the operators ----------------------------------------------------------
def _rule_inputs(T, B=2, Hk=2, Hv=4, dk=8, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(T), 5)
    return (jax.random.normal(ks[0], (B, T, Hk, dk)),
            jax.random.normal(ks[1], (B, T, Hk, dk)),
            jax.random.normal(ks[2], (B, T, Hv, dv)),
            -2.0 * jax.random.uniform(ks[3], (B, T, Hv)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, Hv))))


def _recurrence(q, k, v, g, beta):
    reps = v.shape[2] // q.shape[2]
    q = jnp.repeat(ref.l2norm(q) * q.shape[-1] ** -0.5, reps, axis=2)
    k = jnp.repeat(ref.l2norm(k), reps, axis=2)
    return ref.delta_rule_recurrence(q, k, v, g, beta)


@pytest.mark.parametrize("T,chunk", [(64, 16), (40, 16), (128, 64),
                                     (100, 64), (37, 8)])
def test_chunked_delta_rule_matches_the_recurrence(T, chunk):
    """Values and gradients, at lengths that are and are not a multiple of
    the chunk (the tail is padded with positions that leave the state
    alone)."""
    args = _rule_inputs(T)
    rule = get_op("gated_delta_rule").fn(chunk=chunk)
    want = _recurrence(*args)
    assert float(jnp.max(jnp.abs(rule(*args) - want))) < 1e-5
    ct = jax.random.normal(jax.random.PRNGKey(1), want.shape)
    got_g = jax.grad(lambda *a: jnp.sum(rule(*a) * ct),
                     argnums=range(5))(*args)
    want_g = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * ct),
                      argnums=range(5))(*args)
    for a, b in zip(got_g, want_g):
        assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) < 1e-4


def test_delta_rule_refuses_a_chunk_it_cannot_invert():
    with pytest.raises(mx.MXNetError):
        get_op("gated_delta_rule").fn(chunk=48)


def test_rope_and_causal_conv_match_the_reference():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 3, 16))
    got = get_op("rope").fn(rotary_dim=4, theta=1e7)(x)
    assert jnp.allclose(got, ref.rope(x, 1e7, 4), atol=1e-6)
    assert jnp.array_equal(got[..., 4:], x[..., 4:])
    xc = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(2), (6, 4))
    got = get_op("causal_conv1d").fn(activation="silu")(xc, w)
    assert jnp.allclose(got, ref.silu(ref.causal_conv(xc, w)), atol=1e-6)
    # causal: position t does not see t + 1
    moved = get_op("causal_conv1d").fn()(xc.at[:, 5].add(1.0), w)
    assert jnp.array_equal(moved[:, :5], get_op("causal_conv1d").fn()(xc, w)
                           [:, :5])


# -- layers and the whole net -----------------------------------------------
@pytest.mark.parametrize("index,kind", [(0, "gdn"), (3, "attn")])
def test_each_layer_kind_matches_the_reference(net, index, kind):
    layer = net.layers[index]
    assert layer.full_attention == (kind == "attn")
    x = onp.random.RandomState(index).randn(2, 40, 32).astype("float32")
    weights = {k[len(f"layers.{index}."):]: v
               for k, v in reference.system_weights(net).items()
               if k.startswith(f"layers.{index}.")}
    want, margin, _ = ref.layer(jnp.asarray(x), weights, index, net.config,
                                HELD)
    assert float(margin) == 1.0     # the reference's own choice
    got = layer(mx.np.array(x))._data
    # the residual stream carries x itself: compare what the layer ADDED
    assert _spread_error(got - x, want - x) < 1e-3


def test_whole_net_matches_the_reference_eagerly_and_hybridized(net):
    toks = _tokens(0, (2, 40))
    want = ref.forward(reference.system_weights(net), net.config, toks,
                       experts_held=HELD)["logits"]
    got = net(mx.np.array(toks))._data
    assert got.shape == (2, 40, 96)
    assert reference.logits_error(got, want) < 1e-3
    twin = _net(experts_held=HELD)
    twin.hybridize()
    assert reference.logits_error(twin(mx.np.array(toks))._data, want) < 1e-3
    # the counters of a hybridized net are written back after the call
    counts = twin.layers[0].moe.router.expert_tokens.data().asnumpy()
    assert counts.sum() == 2 * 40 * 4


def test_the_zoo_builds_the_published_sizes_by_default():
    cfg = zoo.QWEN3_NEXT_80B_A3B
    assert cfg["num_experts"] == 512 and cfg["num_experts_per_tok"] == 10
    with pytest.raises(mx.MXNetError):
        zoo.Qwen3NextModel({"hidden_size": 32})
    tiny = model_zoo.qwen3_next_tiny()
    kinds = [layer.full_attention for layer in tiny.layers]
    assert kinds == [False, False, False, True]
    assert tiny.experts_held == (0, 16)


# -- training ---------------------------------------------------------------
def _reference_grads(net, toks, labels):
    weights = reference.system_weights(net)
    value, grads = jax.value_and_grad(
        lambda w: ref.loss(w, net.config, toks, labels, HELD))(weights)
    return float(value), grads


def _grad_error(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-12))


# A_log and dt_bias reach the loss only through the log-decay: their
# gradient is a sum over every position of terms that cancel, and in
# float32 the chunked form (differences of cumulative sums) carries more
# rounding noise than a matrix's gradient; float64 agrees to 1e-5
def _tolerance(name):
    return 2e-2 if name.endswith(("A_log", "dt_bias")) else 2e-3


def test_compile_step_gradients_match_the_reference():
    """One SGD step of rate 1 through ``Trainer.compile_step``: the weights'
    change is the gradient. No fallback, one program."""
    net = _net(experts_held=HELD)
    toks, labels = _tokens(0, (2, 40)), _tokens(1, (2, 40))
    want_loss, want = _reference_grads(net, toks, labels)
    before = {k: onp.asarray(v)
              for k, v in reference.system_weights(net).items()}
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1.0})
    step = trainer.compile_step(net, gluon.loss.SoftmaxCrossEntropyLoss())
    loss = step(mx.np.array(toks), mx.np.array(labels))
    assert step.fallback_reason is None
    assert float(loss.asnumpy()) == pytest.approx(want_loss, rel=1e-5)
    after = reference.system_weights(net)
    for name, p in net.collect_params().items():
        if p.grad_req == "null":
            continue
        err = _grad_error(before[name] - after[name], want[name])
        assert err < _tolerance(name), (name, err)
    # the per-expert token counts left the program as an auxiliary output
    report = telemetry.moe_report()
    mine = [r for r in report["layers"] if r["experts_held"] == HELD][-4:]
    assert all(r["pairs_total"] == 2 * 40 * 4 for r in mine)
    # the compiled text is reachable without the instance, scopes in it
    text = train_step.compiled_modules()["jit_mxtpu_train_step"].as_text()
    for scope in ("gdn", "attn", "router", "experts"):
        assert f"jvp({scope})" in text, scope


def test_eager_record_gradients_match_the_reference(net):
    toks, labels = _tokens(2, (2, 24)), _tokens(3, (2, 24))
    _, want = _reference_grads(net, toks, labels)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net(mx.np.array(toks)), mx.np.array(labels)).mean()
    loss.backward()
    for name, p in net.collect_params().items():
        if p.grad_req != "null":
            err = _grad_error(p.grad()._data, want[name])
            assert err < _tolerance(name), (name, err)


def test_training_through_compile_step_lowers_the_loss():
    net = _net(seed=5, experts_held=HELD)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-3})
    step = trainer.compile_step(net, gluon.loss.SoftmaxCrossEntropyLoss())
    toks = _tokens(4, (2, 33))
    x, y = mx.np.array(toks[:, :-1]), mx.np.array(toks[:, 1:])
    losses = [float(step(x, y).asnumpy()) for _ in range(12)]
    assert step.fallback_reason is None and step._traces == 1
    assert losses[-1] < losses[0] - 0.05, losses


# -- the routed layer's contract --------------------------------------------
def _dense_moe(layer, x, held):
    w = {k: p.data()._data for k, p in layer.collect_params().items()}
    cfg = {"num_experts_per_tok": layer.top_k, "norm_topk_prob": True}
    return ref.sparse_moe(jnp.asarray(x)[None], w, cfg, held)[0][0]


def test_a_skewed_router_drops_no_token():
    """Every token's first choice is one expert: the largest group is the
    whole batch, and all of it is computed."""
    mx.random.seed(0)
    layer = zoo.SparseMoE(16, 8, 32, 4, 8, experts_held=(0, 8))
    layer.initialize()
    x = onp.abs(onp.random.RandomState(0).randn(200, 16)).astype("float32")
    router = onp.array(layer.router.weight.data().asnumpy())
    router[5] = 1.0    # x is positive: expert 5 wins everywhere
    layer.router.weight.set_data(mx.np.array(router))
    got = layer(mx.np.array(x))._data
    _, chosen, _ = get_op("moe_router").fn(top_k=4)(
        jnp.asarray(x), jnp.asarray(router))
    chosen = onp.asarray(chosen)
    assert (chosen == 5).sum() == 200
    row = [r for r in telemetry.moe_report()["layers"]
           if r["experts_held"] == (0, 8)][-1]
    assert row["pairs_here"] == ((chosen >= 0) & (chosen < 8)).sum()
    assert row["max"] == 200 and row["pairs_total"] == 800
    assert row["expert_tokens"] == [float((chosen == e).sum())
                                    for e in range(8)]
    assert _spread_error(got, _dense_moe(layer, x, (0, 8))) < 1e-4


def test_the_sixteen_shares_of_a_layer_add_up_to_the_whole():
    """512 experts, top-10: sixteen layers that hold 32 experts each, with
    the shared expert (which every chip computes alike) counted once, sum to
    the uncut reference's layer."""
    mx.random.seed(1)
    D, F, E, k = 16, 8, 512, 10
    whole = zoo.SparseMoE(D, F, E, k, F)
    whole.initialize()
    x = onp.random.RandomState(1).randn(48, D).astype("float32")
    full = whole.collect_params()
    want = _dense_moe(whole, x, (0, E))
    assert _spread_error(whole(mx.np.array(x))._data, want) < 1e-4
    total, shared = 0.0, None
    for chip in range(16):
        lo, hi = 32 * chip, 32 * chip + 32
        share = zoo.SparseMoE(D, F, E, k, F, experts_held=(lo, hi))
        share.initialize()
        for name, p in share.collect_params().items():
            value = full[name].data()._data
            p.set_data(mx.np.array(
                value[lo:hi] if name in ("gate_up", "down") else value))
        xs = mx.np.array(x)
        y = share(xs)._data
        routed = share.experts(xs, *share.router(xs))._data
        shared = y - routed
        total = total + routed
    assert _spread_error(total + shared, want) < 1e-4


def test_the_reference_takes_a_choice_and_says_how_far_off_it_is(net):
    """Given the system's own choice the margin is 1; given a choice that
    swaps a token's best expert for its worst the margin says so."""
    toks = _tokens(6, (1, 16))
    weights = reference.system_weights(net)
    own = ref.forward(weights, net.config, toks, HELD)
    assert float(own["routing_margin"]) == 1.0
    chosen = []
    hooks = [(layer.moe.router, layer.moe.router.register_forward_hook(
        lambda b, i, out: chosen.append(onp.array(out[1].asnumpy()))))
        for layer in net.layers]
    try:
        net(mx.np.array(toks))
    finally:
        for router, hook in hooks:
            router._forward_hooks.remove(hook)
    same = ref.forward(weights, net.config, toks, HELD, routing=chosen)
    assert float(same["routing_margin"]) == pytest.approx(1.0, abs=1e-5)
    assert reference.logits_error(same["logits"], own["logits"]) < 1e-4
    wrong = [c.copy() for c in chosen]
    for t in range(16):
        others = [e for e in range(16) if e not in wrong[0][t]]
        wrong[0][t, 0] = others[0]
    off = ref.forward(weights, net.config, toks, HELD, routing=wrong)
    assert float(off["routing_margin"]) < 1.0
    # the choice is taken only where the reference's own is a near-tie: with
    # a band no token of this sample reaches, the reference chooses for
    # itself everywhere and the wrong choice is not used at all
    clear = ref.forward(weights, net.config, toks, HELD, routing=wrong,
                        tie_ratio=1.0 + 1e-3)
    assert float(clear["routing_margin"]) == 1.0
    assert reference.logits_error(clear["logits"], own["logits"]) < 1e-6
    for a, b in zip(clear["chosen"], own["chosen"]):
        assert (onp.asarray(a) == onp.asarray(b)).all()
    # with the band at 0 every token takes it (the default)
    every = ref.forward(weights, net.config, toks, HELD, routing=wrong,
                        tie_ratio=0.0)
    assert float(every["routing_margin"]) == float(off["routing_margin"])


def test_routed_experts_says_which_experts_it_cannot_hold():
    from mxnet_tpu.parallel import RoutedExperts

    with pytest.raises(mx.MXNetError):
        RoutedExperts(16, 8, 32, 4, experts_held=(30, 40))
    with pytest.raises(mx.MXNetError):
        RoutedExperts(16, 8, 32, 40)


def test_a_forward_hook_on_the_router_sees_the_choices(net):
    seen = []
    router = net.layers[0].moe.router
    hook = router.register_forward_hook(
        lambda block, inputs, out: seen.append(out[1].asnumpy()))
    try:
        net(mx.np.array(_tokens(5, (1, 12))))
    finally:
        router._forward_hooks.remove(hook)
    assert len(seen) == 1 and seen[0].shape == (12, 4)
    assert seen[0].dtype == onp.int32 and seen[0].max() < 16
