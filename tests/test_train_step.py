"""Whole-step compilation (ISSUE 3): one donated-buffer program per step.

Covers: single-dispatch/zero-recompile accounting via telemetry, numerical
parity with the eager record/backward/``Trainer.step`` loop (SGD+momentum,
Adam, BN aux-stat write-backs), DynamicLossScaler skip-on-overflow
semantics, LR-schedule changes staying recompile-free, the eager fallback
for unsupported optimizers, the data-parallel mesh path, and the bench.py
``train_step`` wiring.

Parity bound: compiled-step and eager results come from DIFFERENT XLA
programs, so FMA contraction may differ (docs/DESIGN.md "Parity bound");
cross-program assertions use tight allclose, not bit-equality.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd as ag, gluon, telemetry as tm
from mxnet_tpu.amp import DynamicLossScaler
from mxnet_tpu.gluon import nn

RTOL, ATOL = 2e-4, 1e-6  # cross-program bound (see module docstring)


@pytest.fixture(autouse=True)
def clean_telemetry():
    tm.disable()
    tm.reset()
    tm.configure(watchdog_warmup_steps=1)
    yield
    tm.disable()
    tm.reset()
    tm.configure(watchdog_warmup_steps=1)


def _make_net(seed=0, bn=True, hidden=16, classes=4, hybridize=False):
    mx.random.seed(seed)
    net = nn.Sequential()
    net.add(nn.Dense(hidden, activation="relu"))
    if bn:
        net.add(nn.BatchNorm())
    net.add(nn.Dense(classes))
    net.initialize()
    if hybridize:
        net.hybridize()
    return net


def _copy_params(src, dst, x):
    src(x), dst(x)  # settle deferred shapes
    for (_, p1), (_, p2) in zip(src.collect_params().items(),
                                dst.collect_params().items()):
        p2.set_data(mx.nd.array(p1.data().asnumpy()))


def _batch(b=16, d=8, classes=4, seed=0):
    rs = onp.random.RandomState(seed)
    x = mx.nd.array(rs.standard_normal((b, d)).astype("float32"))
    y = mx.nd.array(rs.randint(0, classes, (b,)).astype("float32"))
    return x, y


def _eager_steps(net, trainer, loss_fn, batches):
    losses = []
    for x, y in batches:
        with ag.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    return losses


# -- accounting -------------------------------------------------------------
def test_single_dispatch_zero_recompiles_after_warmup():
    """ISSUE 3 satellite: 3 post-warmup steps, each step's telemetry row
    shows exactly ONE dispatch and zero recompiles; an LR-schedule change
    stays at zero recompiles (hypers are runtime operands)."""
    net = _make_net(hybridize=True)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    step = trainer.compile_step(net, loss_fn)
    assert step.fallback_reason is None
    x, y = _batch()
    tm.enable()
    step(x, y)  # warmup: traces + compiles
    tm.step_report(reset=True)
    for _ in range(3):
        step(x, y)
    rows = tm.step_report()
    assert len(rows) == 3
    for row in rows:
        assert row["dispatches"] == 1, row
        assert row["recompiles"] == 0, row
    # LR changes ride as operands: no new trace, no new program
    trainer.set_learning_rate(0.01)
    trainer.optimizer.lr_scheduler = None  # explicit: plain lr change
    step(x, y)
    trainer.set_learning_rate(0.001)
    step(x, y)
    for row in tm.step_report(reset=True)[-2:]:
        assert row["dispatches"] == 1 and row["recompiles"] == 0, row
    assert step._traces == 1
    for site, st in tm.watchdog_stats().items():
        if site.startswith("train_step"):
            assert st["compiles"] == 1, (site, st)


def test_lr_scheduler_zero_recompiles():
    from mxnet_tpu.lr_scheduler import FactorScheduler

    net = _make_net(seed=3)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.1,
         "lr_scheduler": FactorScheduler(step=1, factor=0.5)})
    step = trainer.compile_step(net, loss_fn)
    x, y = _batch()
    for _ in range(4):
        step(x, y)
    assert step._traces == 1  # schedule decayed every step, one program


# -- parity -----------------------------------------------------------------
@pytest.mark.parametrize("opt_name,opt_kwargs", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-3}),
])
def test_parity_with_eager_step(opt_name, opt_kwargs):
    """Compiled loss and post-step weights (incl. BN running stats and
    optimizer state) match the eager forward/backward/``Trainer.step``
    loop within the cross-program bound."""
    net_c = _make_net(seed=1)
    net_e = _make_net(seed=2)
    x0, _ = _batch()
    _copy_params(net_c, net_e, x0)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr_c = gluon.Trainer(net_c.collect_params(), opt_name, dict(opt_kwargs))
    tr_e = gluon.Trainer(net_e.collect_params(), opt_name, dict(opt_kwargs))
    step = tr_c.compile_step(net_c, loss_fn)
    assert step.fallback_reason is None
    batches = [_batch(seed=s) for s in range(4)]
    compiled_losses = [float(step(x, y).asnumpy()) for x, y in batches]
    eager_losses = _eager_steps(net_e, tr_e, loss_fn, batches)
    onp.testing.assert_allclose(compiled_losses, eager_losses, rtol=1e-5)
    for (name, p1), (_, p2) in zip(net_c.collect_params().items(),
                                   net_e.collect_params().items()):
        onp.testing.assert_allclose(
            p1.data().asnumpy(), p2.data().asnumpy(),
            rtol=RTOL, atol=ATOL, err_msg=name)
    assert tr_c.optimizer.num_update == tr_e.optimizer.num_update
    # optimizer state advanced identically (momentum / Adam moments)
    for i in step._train_idx:  # same param order in both trainers
        st_c, st_e = tr_c._states[i], tr_e._states[i]
        assert st_e is not None
        for k in st_c:
            onp.testing.assert_allclose(
                st_c[k].asnumpy(), st_e[k].asnumpy(),
                rtol=RTOL, atol=ATOL, err_msg=f"state {k}")


def test_dynamic_loss_scaler_skip_on_overflow_parity():
    """Overflowing scaled grads skip the update in BOTH paths: weights and
    the LR schedule stay put, the scale halves, and the next clean step
    trains identically."""
    net_c = _make_net(seed=4, bn=False)
    net_e = _make_net(seed=5, bn=False)
    x0, _ = _batch()
    _copy_params(net_c, net_e, x0)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr_c = gluon.Trainer(net_c.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})
    tr_e = gluon.Trainer(net_e.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})
    sc_c = amp.attach_loss_scaler(tr_c, DynamicLossScaler(init_scale=1024.0))
    sc_e = DynamicLossScaler(init_scale=1024.0)
    step = tr_c.compile_step(net_c, loss_fn)
    assert step.loss_scaler is sc_c

    def eager_scaled_step(x, y):
        with ag.record():
            loss = loss_fn(net_e(x), y).mean()
            head = loss * float(sc_e.loss_scale)
        head.backward()
        if sc_e.has_overflow(tr_e._params):
            sc_e.update_scale(True)
            return loss
        for p in tr_e._params:
            if p.grad_req != "null":
                g = p.grad()
                g._set_data(g._data / sc_e.loss_scale)
        sc_e.update_scale(False)
        tr_e.step(1)
        return loss

    # clean step first: both paths train
    x, y = _batch(seed=10)
    step(x, y)
    eager_scaled_step(x, y)
    snap = {n: p.data().asnumpy().copy()
            for n, p in net_c.collect_params().items()}
    # overflow step: non-finite input -> non-finite scaled grads
    x_bad = mx.nd.array(onp.full((16, 8), onp.inf, onp.float32))
    step(x_bad, y)
    eager_scaled_step(x_bad, y)
    for (n, p1), (_, p2) in zip(net_c.collect_params().items(),
                                net_e.collect_params().items()):
        onp.testing.assert_array_equal(p1.data().asnumpy(), snap[n],
                                       err_msg=f"{n} moved on overflow")
        onp.testing.assert_allclose(p1.data().asnumpy(), p2.data().asnumpy(),
                                    rtol=RTOL, atol=ATOL)
    assert sc_c.loss_scale == sc_e.loss_scale == 512.0
    assert sc_c._unskipped == sc_e._unskipped
    assert tr_c.optimizer.num_update == tr_e.optimizer.num_update == 1
    # recovery: the next clean step trains again, identically
    x2, y2 = _batch(seed=11)
    step(x2, y2)
    eager_scaled_step(x2, y2)
    assert tr_c.optimizer.num_update == 2
    for (n, p1), (_, p2) in zip(net_c.collect_params().items(),
                                net_e.collect_params().items()):
        assert not onp.array_equal(p1.data().asnumpy(), snap[n]), n
        onp.testing.assert_allclose(p1.data().asnumpy(), p2.data().asnumpy(),
                                    rtol=RTOL, atol=ATOL, err_msg=n)


# -- fallback ---------------------------------------------------------------
def test_fallback_unsupported_optimizer_still_trains():
    """SGLD declares no fusable recurrence (host RNG): compile_step warns
    once, records the reason, and the eager path still trains."""
    net = _make_net(seed=6, bn=False)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgld",
                            {"learning_rate": 0.01})
    step = trainer.compile_step(net, loss_fn)
    assert step.fallback_reason is not None
    assert "SGLD" in step.fallback_reason
    x, y = _batch()
    net(x)  # settle shapes
    before = {n: p.data().asnumpy().copy()
              for n, p in net.collect_params().items()}
    with pytest.warns(RuntimeWarning, match="falling back"):
        loss = step(x, y)
    assert onp.isfinite(loss.asnumpy()).all()
    assert any(not onp.array_equal(p.data().asnumpy(), before[n])
               for n, p in net.collect_params().items())
    import warnings as _warnings

    with _warnings.catch_warnings(record=True) as rec:  # fires once only
        _warnings.simplefilter("always")
        step(x, y)
    assert not [w for w in rec if issubclass(w.category, RuntimeWarning)]


def test_step_fn_requires_compile():
    from mxnet_tpu.base import MXNetError

    net = _make_net(seed=7)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    with pytest.raises(MXNetError, match="compile_step"):
        trainer.step_fn


# -- mesh (data parallel) ---------------------------------------------------
def test_mesh_data_parallel_matches_single_device():
    """Under a dp mesh the program shards the batch and pmean-reduces
    grads/loss in-program — same math as the full batch on one device."""
    from mxnet_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()  # all 8 virtual CPU devices on 'dp'
    net_m = _make_net(seed=8, bn=False)
    net_s = _make_net(seed=9, bn=False)
    x0, _ = _batch()
    _copy_params(net_m, net_s, x0)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr_m = gluon.Trainer(net_m.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})
    tr_s = gluon.Trainer(net_s.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})
    step_m = tr_m.compile_step(net_m, loss_fn, mesh=mesh)
    step_s = tr_s.compile_step(net_s, loss_fn)
    for seed in range(3):
        x, y = _batch(seed=seed)
        lm = float(step_m(x, y).asnumpy())
        ls = float(step_s(x, y).asnumpy())
        assert abs(lm - ls) < 1e-4, (lm, ls)
    for (n, p1), (_, p2) in zip(net_m.collect_params().items(),
                                net_s.collect_params().items()):
        onp.testing.assert_allclose(p1.data().asnumpy(), p2.data().asnumpy(),
                                    rtol=RTOL, atol=ATOL, err_msg=n)


def test_mesh_batch_divisibility_checked():
    """Ragged batches pad in-program by default; ``strict_batch=True``
    restores the hard error."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel.mesh import make_mesh

    net = _make_net(seed=12, bn=False)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = trainer.compile_step(net, loss_fn, mesh=make_mesh())
    x, y = _batch(b=13)  # 13 rows over 8 shards: pads to 16
    net(x)
    assert onp.isfinite(float(step(x, y).asnumpy()))

    net2 = _make_net(seed=12, bn=False)
    trainer2 = gluon.Trainer(net2.collect_params(), "sgd",
                             {"learning_rate": 0.1})
    strict = trainer2.compile_step(net2, loss_fn, mesh=make_mesh(),
                                   strict_batch=True)
    net2(x)
    with pytest.raises(MXNetError, match="not divisible"):
        strict(x, y)


# -- bench wiring -----------------------------------------------------------
def test_bench_train_step_small(monkeypatch):
    """bench.py train_step (small model): one dispatch per step, zero
    post-warmup recompiles, and a positive compiled-vs-eager ratio."""
    import bench

    monkeypatch.setenv("BENCH_TRAIN_STEP_SMALL", "1")
    r = bench.bench_train_step()
    assert r["dispatches_per_step"] == 1, r
    assert r["recompiles_after_warmup"] == 0, r
    assert r["compiled_programs"] == 1, r
    assert r["value"] > 0 and r["vs_baseline"] > 0, r


def _released_dense(grad_req="write"):
    """A Dense layer after one compiled step: its gradient buffers are
    released. Returns (net, loss_fn, trainer, step, x, y, first loss)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    mx.random.seed(0)
    net = nn.Dense(4, in_units=6)
    net.initialize()
    if grad_req != "write":
        for p in net.collect_params().values():
            p.grad_req = grad_req
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = mx.np.array(onp.random.RandomState(0).randn(8, 6).astype("float32"))
    y = mx.np.array(onp.random.RandomState(1).randn(8, 4).astype("float32"))
    step = trainer.compile_step(net, loss_fn)
    first = float(step(x, y).asnumpy())
    assert step.fallback_reason is None
    return net, loss_fn, trainer, step, x, y, first


def _held_bytes(param):
    return param.data()._grad._data.nbytes


def test_compiled_step_lets_go_of_the_eager_gradient_buffers():
    """The compiled program keeps its gradients to itself: the per-parameter
    buffers ``initialize`` attached for the eager tape are dead memory (4
    bytes a parameter) once the step is built, and ``Parameter.release_grad``
    leaves a scalar zero in their place. What a user sees does not change:
    ``grad()`` is a zero array of the parameter's shape, and an eager
    backward afterwards binds full-size gradients again, so the two ways to
    train still mix."""
    from mxnet_tpu import autograd

    net, loss_fn, trainer, step, x, y, first = _released_dense()
    assert _held_bytes(net.weight) == 4 and _held_bytes(net.bias) == 4
    with autograd.record():
        loss = loss_fn(net(x), y).mean()
    loss.backward()
    assert _held_bytes(net.weight) == 4 * 24
    assert net.weight.grad().shape == (4, 6)
    assert float(onp.abs(net.weight.grad().asnumpy()).max()) > 0
    trainer.step(1)
    assert float(step(x, y).asnumpy()) < first


def test_a_released_gradient_reads_as_full_zeros():
    """Whoever asks for a released gradient gets the zeros it held before,
    at the parameter's shape (and the buffer is back from then on)."""
    net = _released_dense()[0]
    assert _held_bytes(net.weight) == 4
    g = net.weight.grad()
    assert g.shape == (4, 6) and not g.asnumpy().any()
    assert _held_bytes(net.weight) == 4 * 24
    assert [a.shape for a in net.bias.list_grad()] == [(4,)]


def test_zero_grad_between_a_compiled_step_and_an_eager_one():
    """``zero_grad`` on released buffers leaves them released and zero; the
    eager step after it trains as it would have."""
    from mxnet_tpu import autograd

    net, loss_fn, trainer, step, x, y, first = _released_dense()
    net.zero_grad()
    assert _held_bytes(net.weight) == 4
    with autograd.record():
        loss = loss_fn(net(x), y).mean()
    loss.backward()
    eager = net.weight.grad().asnumpy().copy()
    assert eager.shape == (4, 6) and onp.abs(eager).max() > 0
    net.zero_grad()
    assert not net.weight.grad().asnumpy().any()
    assert net.weight.grad().shape == (4, 6)
    trainer.allreduce_grads()
    trainer.update(1)
    assert float(step(x, y).asnumpy()) < first


def test_grad_req_add_accumulates_into_a_released_buffer():
    """``grad_req='add'``: the first eager backward after a compiled step
    adds into the scalar zero that stands in (a broadcast), the second into
    the full buffer: twice the gradient of one backward."""
    from mxnet_tpu import autograd

    net, loss_fn, trainer, step, x, y, _ = _released_dense("add")
    assert _held_bytes(net.weight) == 4
    for _ in range(2):
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
    twice = net.weight.grad().asnumpy().copy()
    net.zero_grad()
    with autograd.record():
        loss = loss_fn(net(x), y).mean()
    loss.backward()
    once = net.weight.grad().asnumpy()
    assert once.shape == (4, 6) and onp.abs(once).max() > 0
    onp.testing.assert_allclose(twice, 2 * once, rtol=1e-5, atol=1e-7)
