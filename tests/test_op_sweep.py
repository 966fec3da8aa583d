"""Registry-wide operator sweep (round-2 VERDICT item #2).

Every op in the registry must be accounted for: either swept here
(forward vs a NumPy oracle across dtypes + edge shapes, and a numeric
gradient check when differentiable) or explicitly mapped to the dedicated
test file that covers it. ``test_registry_fully_covered`` enforces the
invariant, so newly registered ops fail CI until they get coverage.

Reference pattern: tests/python/unittest/test_numpy_op.py (op-by-op with
dtype matrices) + test_utils.py check_numeric_gradient (:1043).
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import _core
from mxnet_tpu.ops.registry import _OPS, apply_op
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient

RNG = onp.random.RandomState(7)


def zlib_seed(name):
    import zlib

    return zlib.crc32(name.encode()) % (2 ** 31)


def _reseed(name):
    """Per-op deterministic seed: adding/removing sweep entries must not
    shift the RNG stream of unrelated ops (a near-tie in min/max inputs
    makes their numeric gradient unstable)."""
    RNG.seed(zlib_seed(name))


# ---------------------------------------------------------------------------
# element-wise table ops: domains + oracles derived from the op tables
# ---------------------------------------------------------------------------
# sample domain per op (low, high, offset); default (-1, 1)
_DOMAIN = {
    "log": (0.1, 3.0), "log2": (0.1, 3.0), "log10": (0.1, 3.0),
    "log1p": (-0.5, 3.0), "sqrt": (0.05, 3.0), "cbrt": (0.05, 3.0),
    "reciprocal": (0.5, 2.0), "arccosh": (1.1, 3.0),
    "arctanh": (-0.9, 0.9), "arcsin": (-0.9, 0.9), "arccos": (-0.9, 0.9),
    "gamma": (0.5, 3.0), "gammaln": (0.5, 3.0), "erfinv": (-0.9, 0.9),
    "float_power": (0.2, 2.0), "true_divide": (0.5, 2.0),
    "divide": (0.5, 2.0), "mod": (0.5, 2.0), "fmod": (0.5, 2.0),
    "remainder": (0.5, 2.0), "floor_divide": (0.5, 2.0),
    "power": (0.2, 2.0), "logaddexp": (-2.0, 2.0), "hypot": (0.1, 2.0),
    "heaviside": (-1.0, 1.0), "i0": (-2.0, 2.0),
}
# ops whose jnp name differs from numpy's, or that numpy lacks → no oracle
_NO_ORACLE = {
    "sigmoid", "relu", "softsign", "erf", "erfinv", "gamma", "gammaln",
    "stop_gradient", "copy", "fix",
}
# integer-only elementwise ops
_INT_ONLY = {"invert", "bitwise_and", "bitwise_or", "bitwise_xor",
             "left_shift", "right_shift", "gcd", "lcm"}
_BOOL_OK = {"logical_not", "logical_and", "logical_or", "logical_xor"}
# not differentiable / piecewise-constant → skip numeric-gradient
_NO_GRAD = _INT_ONLY | _BOOL_OK | {
    "sign", "floor", "ceil", "trunc", "rint", "fix", "isnan", "isinf",
    "isfinite", "isposinf", "isneginf", "signbit", "equal", "not_equal",
    "greater", "greater_equal", "less", "less_equal", "heaviside",
    "stop_gradient", "conj", "real", "imag", "angle", "copysign",
    "nextafter", "ldexp", "maximum", "minimum", "fmax", "fmin",
    "copy", "positive", "negative", "abs", "nan_to_num",
    "mod", "fmod", "remainder", "floor_divide", "rad2deg", "deg2rad",
    "degrees", "radians", "round", "around", "round_", "fabs",
    "logaddexp2", "float_power", "true_divmod", "i0",
}

_UNARY_NAMES = sorted(set(_core._UNARY) | set(_core._EXTRA_UNARY))
_BINARY_NAMES = sorted(n for n in _core._BINARY
                       if n not in ("matmul", "dot"))


def _sample(name, shape, dtype="float32"):
    lo, hi = _DOMAIN.get(name, (-1.0, 1.0))
    if dtype == "bool":
        return RNG.rand(*shape) > 0.5
    if dtype in ("int32", "int64", "uint8"):
        return RNG.randint(1, 5, size=shape).astype(dtype)
    return RNG.uniform(lo, hi, size=shape).astype(dtype)


def _dtypes_for(name):
    if name in _INT_ONLY:
        return ["int32"]
    if name in _BOOL_OK:
        return ["bool"]
    return ["float32", "bfloat16"]


def _oracle(name):
    if name in _NO_ORACLE:
        return None
    return getattr(onp, name, None)


@pytest.mark.parametrize("name", _UNARY_NAMES)
def test_unary_forward(name):
    for dtype in _dtypes_for(name):
        for shape in [(3, 4), (2, 0, 3), (), (1,)]:
            x = _sample(name, shape, dtype)
            got = apply_op(name, NDArray(x)).asnumpy()
            ref_fn = _oracle(name)
            if ref_fn is not None and dtype == "float32":
                want = ref_fn(x)
                assert_almost_equal(got.astype("float64"),
                                    onp.asarray(want).astype("float64"),
                                    rtol=2e-3, atol=1e-4)
            else:
                assert got.shape == onp.asarray(
                    _core._UNARY.get(name, _core._EXTRA_UNARY.get(name))(x)
                ).shape


@pytest.mark.parametrize("name", _BINARY_NAMES)
def test_binary_forward(name):
    for dtype in _dtypes_for(name):
        shapes = [((3, 4), (3, 4)), ((3, 1), (1, 4)),  # broadcast
                  ((0, 4), (0, 4)), ((), ())]
        for sa, sb in shapes:
            a = _sample(name, sa, dtype)
            b = _sample(name, sb, dtype)
            if name in ("left_shift", "right_shift"):
                b = onp.clip(b, 0, 3)
            if name == "ldexp":
                b = onp.clip(b, -2, 2).astype("int32")
            got = apply_op(name, NDArray(a), NDArray(b)).asnumpy()
            ref_fn = _oracle(name)
            if ref_fn is not None and dtype == "float32":
                want = onp.asarray(ref_fn(a, b))
                assert_almost_equal(got.astype("float64"),
                                    want.astype("float64"),
                                    rtol=2e-3, atol=1e-4)
            else:
                assert got.size == onp.broadcast_shapes(sa, sb)[0] * \
                    got.shape[-1] if got.ndim else True


_GRAD_UNARY = [n for n in _UNARY_NAMES if n not in _NO_GRAD]
_GRAD_BINARY = [n for n in _BINARY_NAMES if n not in _NO_GRAD]


@pytest.mark.parametrize("name", _GRAD_UNARY)
def test_unary_numeric_gradient(name):
    x = NDArray(_sample(name, (2, 3)))
    check_numeric_gradient(
        lambda ins: apply_op(name, ins[0]).sum(), [x])


@pytest.mark.parametrize("name", _GRAD_BINARY)
def test_binary_numeric_gradient(name):
    a = NDArray(_sample(name, (2, 3)))
    b = NDArray(_sample(name, (2, 3)))
    check_numeric_gradient(
        lambda ins: apply_op(name, ins[0], ins[1]).sum(), [a, b])


# ---------------------------------------------------------------------------
# structured specs for the non-table ops
# spec: (build_inputs, attrs, oracle(np arrays)->np | None, grad: bool)
# ---------------------------------------------------------------------------
def _f(*shape):
    return RNG.uniform(-1, 1, size=shape).astype("float32")


def _spd(n):
    a = RNG.randn(n, n).astype("float32")
    return a @ a.T + n * onp.eye(n, dtype="float32")


SPECS = {
    # reductions / stats
    "sum": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: x.sum(1), True),
    "mean": (lambda: [_f(3, 4)], {"axis": 0}, lambda x: x.mean(0), True),
    "max": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: x.max(1), True),
    "min": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: x.min(1), True),
    "prod": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: x.prod(1), True),
    "std": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: x.std(1), True),
    "var": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: x.var(1), True),
    "norm": (lambda: [_f(3, 4)], {}, lambda x: onp.linalg.norm(x), True),
    "logsumexp": (lambda: [_f(3, 4)], {"axis": 1},
                  lambda x: onp.log(onp.exp(x).sum(1)), True),
    "all": (lambda: [RNG.rand(3, 4) > 0.5], {"axis": 1},
            lambda x: x.all(1), False),
    "any": (lambda: [RNG.rand(3, 4) > 0.5], {"axis": 1},
            lambda x: x.any(1), False),
    "nansum": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: onp.nansum(x, 1),
               True),
    "nanmean": (lambda: [_f(3, 4)], {"axis": 1},
                lambda x: onp.nanmean(x, 1), True),
    "nanmax": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: onp.nanmax(x, 1),
               False),
    "nanmin": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: onp.nanmin(x, 1),
               False),
    "median": (lambda: [_f(3, 5)], {"axis": 1},
               lambda x: onp.median(x, 1), False),
    "quantile": (lambda: [_f(3, 5)], {"q": 0.5, "axis": 1},
                 lambda x: onp.quantile(x, 0.5, axis=1), False),
    "percentile": (lambda: [_f(3, 5)], {"q": 50.0, "axis": 1},
                   lambda x: onp.percentile(x, 50.0, axis=1), False),
    "average": (lambda: [_f(3, 4), onp.abs(_f(3, 4)) + 0.1],
                {"axis": 1}, lambda x, w: onp.average(x, 1, w), True),
    "cumsum": (lambda: [_f(3, 4)], {"axis": 1},
               lambda x: onp.cumsum(x, 1), True),
    "cumprod": (lambda: [_f(3, 4)], {"axis": 1},
                lambda x: onp.cumprod(x, 1), True),
    "diff": (lambda: [_f(3, 5)], {"axis": 1}, lambda x: onp.diff(x, axis=1),
             True),
    "ediff1d": (lambda: [_f(6)], {}, lambda x: onp.ediff1d(x), True),
    "trace": (lambda: [_f(4, 4)], {}, lambda x: onp.trace(x), True),
    "cov": (lambda: [_f(3, 8)], {}, lambda x: onp.cov(x), False),
    "corrcoef": (lambda: [_f(3, 8)], {}, lambda x: onp.corrcoef(x), False),
    "bincount": (lambda: [onp.array([0, 1, 1, 3])],
                 {"length": 5},
                 lambda x: onp.bincount(x, minlength=5)[:5], False),
    "histogram_bounded": (lambda: [_f(32)], {"bins": 4, "range": (-1, 1)},
                          None, False),
    "digitize": (lambda: [_f(8), onp.linspace(-1, 1, 4).astype("float32")],
                 {}, lambda x, b: onp.digitize(x, b), False),
    # shape / indexing
    "reshape": (lambda: [_f(3, 4)], {"newshape": (4, 3)},
                lambda x: x.reshape(4, 3), True),
    "transpose": (lambda: [_f(3, 4)], {"axes": (1, 0)}, lambda x: x.T, True),
    "swapaxes": (lambda: [_f(3, 4, 2)], {"axis1": 0, "axis2": 2},
                 lambda x: x.swapaxes(0, 2), True),
    "moveaxis": (lambda: [_f(3, 4, 2)], {"source": 0, "destination": 2},
                 lambda x: onp.moveaxis(x, 0, 2), True),
    "expand_dims": (lambda: [_f(3, 4)], {"axis": 1},
                    lambda x: x[:, None], True),
    "squeeze": (lambda: [_f(3, 1, 4)], {"axis": 1},
                lambda x: x.squeeze(1), True),
    "flatten": (lambda: [_f(3, 4)], {}, lambda x: x.reshape(3, -1), True),
    "broadcast_to": (lambda: [_f(1, 4)], {"shape": (3, 4)},
                     lambda x: onp.broadcast_to(x, (3, 4)), True),
    "tile": (lambda: [_f(2, 3)], {"reps": (2, 2)},
             lambda x: onp.tile(x, (2, 2)), True),
    "repeat": (lambda: [_f(2, 3)], {"repeats": 2, "axis": 1},
               lambda x: onp.repeat(x, 2, 1), True),
    "flip": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: onp.flip(x, 1),
             True),
    "roll": (lambda: [_f(3, 4)], {"shift": 1, "axis": 1},
             lambda x: onp.roll(x, 1, 1), True),
    "rot90": (lambda: [_f(3, 4)], {}, lambda x: onp.rot90(x), True),
    "concatenate": (lambda: [_f(2, 3), _f(2, 3)], {"axis": 0},
                    lambda a, b: onp.concatenate([a, b], 0), True),
    "stack": (lambda: [_f(2, 3), _f(2, 3)], {"axis": 0},
              lambda a, b: onp.stack([a, b], 0), True),
    "split": (lambda: [_f(4, 3)], {"indices_or_sections": 2, "axis": 0},
              None, False),
    "array_split": (lambda: [_f(5, 3)], {"indices_or_sections": 2,
                                         "axis": 0}, None, False),
    "atleast_1d": (lambda: [_f()], {}, lambda x: onp.atleast_1d(x), False),
    "atleast_2d": (lambda: [_f(3)], {}, lambda x: onp.atleast_2d(x), False),
    "atleast_3d": (lambda: [_f(3, 4)], {}, lambda x: onp.atleast_3d(x),
                   False),
    "pad": (lambda: [_f(3, 4)], {"pad_width": ((1, 1), (0, 0))},
            lambda x: onp.pad(x, ((1, 1), (0, 0))), True),
    "diag": (lambda: [_f(4, 4)], {}, lambda x: onp.diag(x), True),
    "diagonal": (lambda: [_f(3, 4)], {}, lambda x: onp.diagonal(x), True),
    "tril": (lambda: [_f(4, 4)], {}, lambda x: onp.tril(x), True),
    "triu": (lambda: [_f(4, 4)], {}, lambda x: onp.triu(x), True),
    "tril_indices_from": (lambda: [_f(4, 4)], {}, None, False),
    "clip": (lambda: [_f(3, 4) * 0.4], {"a_min": -0.5,
                                                "a_max": 0.5},
             lambda x: onp.clip(x * 1.0, -0.5, 0.5), True),
    "where": (lambda: [RNG.rand(3, 4) > 0.5, _f(3, 4), _f(3, 4)], {},
              lambda c, a, b: onp.where(c, a, b), False),
    "take": (lambda: [_f(5, 3), onp.array([0, 2, 4])], {"axis": 0},
             lambda x, i: onp.take(x, i, 0), False),
    "take_along_axis": (
        lambda: [_f(3, 4), onp.argsort(RNG.rand(3, 4), 1)], {"axis": 1},
        lambda x, i: onp.take_along_axis(x, i, 1), False),
    "gather_nd": (lambda: [_f(3, 4), onp.array([[0, 1], [1, 2]]).T], {},
                  None, False),
    "pick": (lambda: [_f(3, 4), onp.array([0., 1., 2.])], {"axis": 1},
             None, False),
    "one_hot": (lambda: [onp.array([0, 2, 1])], {"depth": 4},
                lambda i: onp.eye(4, dtype="float32")[i], False),
    "astype": (lambda: [_f(3, 4)], {"dtype": "int32"},
               lambda x: x.astype("int32"), False),
    "argmax": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: x.argmax(1),
               False),
    "argmin": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: x.argmin(1),
               False),
    "argsort": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: x.argsort(1),
                False),
    "sort": (lambda: [_f(3, 4)], {"axis": 1}, lambda x: onp.sort(x, 1),
             True),
    "topk": (lambda: [_f(3, 6)], {"k": 2}, None, False),
    "searchsorted": (lambda: [onp.sort(_f(6)), _f(4)], {},
                     lambda a, v: onp.searchsorted(a, v), False),
    "round": (lambda: [_f(3, 4)], {}, lambda x: onp.round(x), False),
    "unravel_index": (lambda: [onp.array([1, 5, 7])], {"shape": (3, 4)},
                      None, False),
    "ravel_multi_index": (
        lambda: [onp.array([[0, 1], [1, 2]])], {"shape": (3, 4)},
        lambda m: onp.ravel_multi_index(tuple(m), (3, 4)), False),
    "flatnonzero_bounded": (lambda: [_f(8)], {"size": 8}, None, False),
    "meshgrid": (lambda: [_f(3), _f(4)], {}, None, False),
    "interp": (lambda: [_f(5), onp.linspace(-1, 1, 4).astype("float32"),
                        _f(4)], {}, None, False),
    # linear algebra (oracle via reconstruction where sign conventions vary)
    "linalg_svd": (lambda: [_f(4, 3)], {}, None, False),
    "linalg_qr": (lambda: [_f(4, 3)], {}, None, False),
    "linalg_slogdet": (lambda: [_spd(3)], {}, None, False),
    "linalg_solve": (lambda: [_spd(3), _f(3, 2)], {},
                     lambda a, b: onp.linalg.solve(a, b), False),
    "linalg_lstsq": (lambda: [_f(5, 3), _f(5, 2)], {}, None, False),
    "linalg_matrix_power": (lambda: [_spd(3)], {"n": 2},
                            lambda a: onp.linalg.matrix_power(a, 2), False),
    "linalg_multi_dot": (lambda: [_f(3, 4), _f(4, 5), _f(5, 2)], {},
                         lambda *xs: onp.linalg.multi_dot(xs), False),
    "linalg_tensorsolve": (lambda: [RNG.randn(2, 3, 6).astype("float32"),
                                    _f(2, 3)], {}, None, False),
    "linalg_tensorinv": (lambda: [RNG.randn(2, 3, 2, 3).astype("float32") +
                                  onp.eye(6).reshape(2, 3, 2, 3)], {"ind": 2},
                         None, False),
    "einsum": (lambda: [_f(3, 4), _f(4, 5)], {"subscripts": "ij,jk->ik"},
               lambda a, b: onp.einsum("ij,jk->ik", a, b), True),
    "tensordot": (lambda: [_f(3, 4), _f(4, 5)], {"axes": 1},
                  lambda a, b: onp.tensordot(a, b, 1), True),
    "cross": (lambda: [_f(3), _f(3)], {}, lambda a, b: onp.cross(a, b),
              True),
    "fft": (lambda: [_f(8)], {}, lambda x: onp.fft.fft(x), False),
    "ifft": (lambda: [_f(8)], {}, lambda x: onp.fft.ifft(x), False),
    "rfft": (lambda: [_f(8)], {}, lambda x: onp.fft.rfft(x), False),
    "irfft": (lambda: [_f(5)], {}, None, False),
    # NN ops: forward smoke + gradient via sum-loss (numerics covered in
    # dedicated files; this guarantees sweep presence)
    "fully_connected": (lambda: [_f(2, 3), _f(4, 3), _f(4)],
                        {"num_hidden": 4}, None, True),
    "convolution": (lambda: [_f(1, 2, 5, 5), _f(3, 2, 3, 3), _f(3)],
                    {"kernel": (3, 3), "num_filter": 3}, None, True),
    "deconvolution": (lambda: [_f(1, 2, 5, 5), _f(2, 3, 3, 3), _f(3)],
                      {"kernel": (3, 3), "num_filter": 3}, None, False),
    "pooling": (lambda: [_f(1, 2, 6, 6)], {"kernel": (2, 2),
                                           "stride": (2, 2)}, None, True),
    "adaptive_avg_pool2d": (lambda: [_f(1, 2, 6, 6)], {"output_size": 2},
                            None, True),
    "softmax": (lambda: [_f(3, 5)], {"axis": -1}, None, True),
    "log_softmax": (lambda: [_f(3, 5)], {"axis": -1}, None, True),
    "masked_softmax": (lambda: [_f(3, 5), RNG.rand(3, 5) > 0.3], {},
                       None, False),
    "activation": (lambda: [_f(3, 4)], {"act_type": "relu"}, None, False),
    "leaky_relu": (lambda: [_f(3, 4)], {"act_type": "leaky", "slope": 0.1},
                   None, True),
    "smooth_l1": (lambda: [_f(3, 4)], {"scalar": 1.0}, None, True),
    "embedding": (lambda: [onp.array([0, 2, 1]), _f(5, 4)], {}, None,
                  False),
    "sequence_mask": (lambda: [_f(4, 2, 3), onp.array([2., 4.])],
                      {"use_sequence_length": True}, None, False),
    "sequence_reverse": (lambda: [_f(4, 2, 3)], {}, None, False),
    "sequence_last": (lambda: [_f(4, 2, 3)], {}, None, False),
    "layer_norm": (lambda: [_f(3, 4), _f(4), _f(4)], {}, None, True),
    "rms_norm": (lambda: [_f(3, 4), _f(4)], {}, None, True),
    "group_norm": (lambda: [_f(2, 4, 3), _f(4), _f(4)], {"num_groups": 2},
                   None, False),
    "instance_norm": (lambda: [_f(2, 3, 4), _f(3), _f(3)], {}, None, False),
    "moments": (lambda: [_f(3, 4)], {"axes": (0,)}, None, False),
    # vision tier
    "box_iou": (lambda: [onp.abs(_f(4, 4)), onp.abs(_f(5, 4))], {}, None,
                False),
    "upsampling": (lambda: [_f(1, 2, 3, 3)], {"scale": 2}, None, True),
    "bilinear_resize_2d": (lambda: [_f(1, 2, 4, 4)],
                           {"height": 8, "width": 8}, None, True),
    "roi_pooling": (lambda: [_f(1, 2, 8, 8),
                             onp.array([[0, 0, 0, 4, 4]], "float32")],
                    {"pooled_size": (2, 2)}, None, False),
    "roi_align": (lambda: [_f(1, 2, 8, 8),
                           onp.array([[0, 1, 1, 6, 6]], "float32")],
                  {"pooled_size": (2, 2)}, None, True),
    "box_decode": (lambda: [_f(2, 4, 4), onp.abs(_f(2, 4, 4))], {}, None,
                   False),
    "nan_to_num": (lambda: [onp.array([[onp.nan, 1.0, -onp.inf]],
                                       "float32")], {},
                   lambda x: onp.nan_to_num(x, posinf=None, neginf=None),
                   False),
    "heaviside": (lambda: [_f(3, 4), _f(3, 4)], {},
                  lambda a, b: onp.heaviside(a, b), False),
    "float_power": (lambda: [onp.abs(_f(3, 4)) + 0.2, _f(3, 4)], {},
                    lambda a, b: onp.float_power(a, b), False),
    # misc numerics
    "inner": (lambda: [_f(3), _f(3)], {}, lambda a, b: onp.inner(a, b),
              True),
    "outer": (lambda: [_f(3), _f(4)], {}, lambda a, b: onp.outer(a, b),
              True),
    "vdot": (lambda: [_f(4), _f(4)], {}, lambda a, b: onp.vdot(a, b), True),
    "kron": (lambda: [_f(2, 2), _f(2, 2)], {},
             lambda a, b: onp.kron(a, b), True),
}

def _fill_diag_ref(x, val):
    y = x.copy()
    onp.fill_diagonal(y, val)
    return y


# ---------------------------------------------------------------------------
# specs for the breadth tiers (ops/extra.py, ops/linalg_legacy.py,
# ops/optimizer_ops.py)
# ---------------------------------------------------------------------------
def _tri_vec(n):
    return _f(n * (n + 1) // 2)


SPECS.update({
    # extra.py — tensor / transformer / multibox
    "batch_dot": (lambda: [_f(2, 3, 4), _f(2, 4, 5)], {},
                  lambda a, b: onp.matmul(a, b), True),
    "khatri_rao": (lambda: [_f(2, 4), _f(3, 4)], {},
                   lambda a, b: onp.stack(
                       [onp.kron(a[:, i], b[:, i])
                        for i in range(4)], 1).reshape(6, 4), True),
    "interleaved_matmul_selfatt_qk": (
        lambda: [_f(6, 2, 3 * 2 * 4)], {"heads": 2}, None, True),
    "interleaved_matmul_selfatt_valatt": (
        lambda: [_f(6, 2, 3 * 2 * 4), _f(4, 6, 6)], {"heads": 2}, None,
        True),
    "interleaved_matmul_encdec_qk": (
        lambda: [_f(5, 2, 2 * 4), _f(7, 2, 2 * 2 * 4)], {"heads": 2},
        None, True),
    "interleaved_matmul_encdec_valatt": (
        lambda: [_f(7, 2, 2 * 2 * 4), _f(4, 5, 7)], {"heads": 2}, None,
        True),
    "depth_to_space": (lambda: [_f(1, 8, 2, 3)], {"block_size": 2}, None,
                       True),
    "space_to_depth": (lambda: [_f(1, 2, 4, 6)], {"block_size": 2}, None,
                       True),
    "im2col": (lambda: [_f(1, 2, 5, 5)], {"kernel": (3, 3)}, None, True),
    "col2im": (lambda: [_f(1, 2 * 9, 9)], {"output_size": (5, 5),
                                           "kernel": (3, 3)}, None, True),
    "reverse": (lambda: [_f(3, 4)], {"axis": 1},
                lambda x: x[:, ::-1], True),
    "batch_take": (lambda: [_f(3, 5), onp.array([0, 2, 4])], {}, None,
                   False),
    "argmax_channel": (lambda: [_f(3, 4)], {},
                       lambda x: x.argmax(1).astype("float32"), False),
    "shape_array": (lambda: [_f(3, 4)], {},
                    lambda x: onp.array(x.shape), False),
    "size_array": (lambda: [_f(3, 4)], {}, lambda x: onp.array([x.size]),
                   False),
    "arange_like": (lambda: [_f(3, 4)], {},
                    lambda x: onp.arange(12.0).reshape(3, 4), False),
    "allclose": (lambda: [_f(3, 4)] * 1 + [_f(3, 4)], {}, None, False),
    "index_copy": (lambda: [_f(4, 3), onp.array([1, 3]), _f(2, 3)], {},
                   None, False),
    "quadratic": (lambda: [_f(3, 4)], {"a": 1.0, "b": 2.0, "c": 3.0},
                  lambda x: x * x + 2 * x + 3, True),
    "softmin": (lambda: [_f(3, 4)], {}, None, True),
    "masked_log_softmax": (lambda: [_f(3, 5), RNG.rand(3, 5) > 0.3], {},
                           None, False),
    "softmax_cross_entropy": (lambda: [_f(4, 5),
                                       onp.array([0., 1., 2., 3.])], {},
                              None, False),
    "amp_cast": (lambda: [_f(3, 4)], {"dtype": "bfloat16"}, None, False),
    "amp_multicast": (lambda: [_f(3, 4), _f(3, 4)], {"num_outputs": 2},
                      None, False),
    "bipartite_matching": (lambda: [onp.abs(_f(4, 5))], {"threshold": 0.1},
                           None, False),
    "multibox_prior": (lambda: [_f(1, 3, 4, 4)],
                       {"sizes": (0.5, 0.25), "ratios": (1.0, 2.0)}, None,
                       False),
    "multibox_target": (
        lambda: [onp.abs(_f(1, 8, 4)),
                 _f(1, 3, 8),
                 onp.array([[[0, 0.1, 0.1, 0.6, 0.6],
                             [1, 0.4, 0.4, 0.9, 0.9],
                             [-1, 0, 0, 0, 0]]], "float32")], {}, None,
        False),
    "multibox_detection": (
        lambda: [onp.abs(_f(1, 3, 8)), _f(1, 32),
                 onp.abs(_f(1, 8, 4))], {}, None, False),
    "blackman": (lambda: [], {"M": 8}, lambda: onp.blackman(8), False),
    "hamming": (lambda: [], {"M": 8}, lambda: onp.hamming(8), False),
    "hanning": (lambda: [], {"M": 8}, lambda: onp.hanning(8), False),
    "diagflat": (lambda: [_f(4)], {}, lambda x: onp.diagflat(x), True),
    "fill_diagonal": (lambda: [_f(4, 4)], {"val": 9.0},
                      lambda x: _fill_diag_ref(x, 9.0), False),
    "rollaxis": (lambda: [_f(2, 3, 4)], {"axis": 2},
                 lambda x: onp.rollaxis(x, 2), True),
    "polyval": (lambda: [_f(3), _f(4)], {},
                lambda p, x: onp.polyval(p, x), True),
    "tril_indices": (lambda: [], {"n": 4}, None, False),
    # linalg_legacy.py
    "linalg_gemm": (lambda: [_f(3, 4), _f(4, 5), _f(3, 5)],
                    {"alpha": 2.0, "beta": 0.5},
                    lambda a, b, c: 2.0 * a @ b + 0.5 * c, True),
    "linalg_gemm2": (lambda: [_f(3, 4), _f(5, 4)], {"transpose_b": True},
                     lambda a, b: a @ b.T, True),
    "linalg_potrf": (lambda: [_spd(4)], {},
                     lambda a: onp.linalg.cholesky(a), False),
    "linalg_potri": (lambda: [onp.linalg.cholesky(_spd(4))], {}, None,
                     False),
    "linalg_trmm": (lambda: [_f(4, 4), _f(4, 3)], {},
                    lambda a, b: onp.tril(a) @ b, True),
    "linalg_trsm": (lambda: [_spd(4), _f(4, 3)], {},
                    lambda a, b: onp.linalg.solve(onp.tril(a), b), False),
    "linalg_syrk": (lambda: [_f(3, 4)], {},
                    lambda a: a @ a.T, True),
    "linalg_syevd": (lambda: [_spd(4)], {}, None, False),
    "linalg_gelqf": (lambda: [_f(3, 5)], {}, None, False),
    "linalg_makediag": (lambda: [_f(4)], {},
                        lambda a: onp.diagflat(a), True),
    "linalg_extractdiag": (lambda: [_f(4, 4)], {},
                           lambda a: onp.diagonal(a), True),
    "linalg_maketrian": (lambda: [_tri_vec(4)], {}, None, False),
    "linalg_extracttrian": (lambda: [_f(4, 4)], {}, None, True),
    "linalg_sumlogdiag": (lambda: [_spd(4)], {},
                          lambda a: onp.log(onp.diag(a)).sum(), True),
    "linalg_inverse": (lambda: [_spd(4)], {},
                       lambda a: onp.linalg.inv(a), False),
    "linalg_eig": (lambda: [_f(4, 4)], {}, None, False),
    "linalg_eigvals": (lambda: [_f(4, 4)], {}, None, False),
    # optimizer_ops.py — each checked against a hand-rolled numpy step
    "sgd_update": (lambda: [_f(4), _f(4)], {"lr": 0.1, "wd": 0.01},
                   lambda w, g: w - 0.1 * (g + 0.01 * w), False),
    "sgd_mom_update": (lambda: [_f(4), _f(4), _f(4)],
                       {"lr": 0.1, "momentum": 0.9}, None, False),
    "nag_mom_update": (lambda: [_f(4), _f(4), _f(4)],
                       {"lr": 0.1, "momentum": 0.9}, None, False),
    "signsgd_update": (lambda: [_f(4), _f(4)], {"lr": 0.1},
                       lambda w, g: w - 0.1 * onp.sign(g), False),
    "signum_update": (lambda: [_f(4), _f(4), _f(4)],
                      {"lr": 0.1, "momentum": 0.9}, None, False),
    "adam_update": (lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4))],
                    {"lr": 0.01}, None, False),
    "adamw_update": (lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4))],
                     {"lr": 0.01, "wd": 0.01}, None, False),
    "adabelief_update": (lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4))],
                         {"lr": 0.01}, None, False),
    "ftml_update": (lambda: [_f(4), _f(4), onp.abs(_f(4)),
                             onp.abs(_f(4)), _f(4)], {"lr": 0.01, "t": 2},
                    None, False),
    "ftrl_update": (lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4))],
                    {"lr": 0.1}, None, False),
    "rmsprop_update": (lambda: [_f(4), _f(4), onp.abs(_f(4))],
                       {"lr": 0.01}, None, False),
    "rmspropalex_update": (lambda: [_f(4), _f(4), onp.abs(_f(4)), _f(4),
                                    _f(4)], {"lr": 0.01}, None, False),
    "lamb_update_phase1": (lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4))],
                           {"t": 1}, None, False),
    "lamb_update_phase2": (lambda: [_f(4), _f(4), onp.array([1.0]),
                                    onp.array([1.0])], {"lr": 0.01}, None,
                           False),
    "sparse_sgd_update": (lambda: [_f(6, 3), _f(2, 3),
                                   onp.array([1, 4])], {"lr": 0.1}, None,
                          False),
    "sparse_adagrad_update": (
        lambda: [_f(6, 3), onp.abs(_f(6, 3)), _f(2, 3),
                 onp.array([1, 4])], {"lr": 0.1}, None, False),
    "sparse_adam_update": (
        lambda: [_f(6, 3), _f(6, 3), onp.abs(_f(6, 3)), _f(2, 3),
                 onp.array([1, 4])], {"lr": 0.1, "t": 2.0}, None, False),
    "sparse_ftrl_update": (
        lambda: [_f(6, 3), _f(6, 3), onp.abs(_f(6, 3)), _f(2, 3),
                 onp.array([1, 4])], {"lr": 0.1}, None, False),
    "group_adagrad_update": (lambda: [_f(4, 3), onp.abs(_f(4)), _f(4, 3)],
                             {"lr": 0.1}, None, False),
    # interleaved reference convention: (w0, g0, w1, g1, ...)
    "multi_sgd_update": (lambda: [_f(3), _f(3), _f(4), _f(4)],
                         {"lrs": (0.1, 0.1), "wds": (0.0, 0.0),
                          "num_weights": 2}, None, False),
    "all_finite": (lambda: [_f(3, 4)], {},
                   lambda x: onp.array(True), False),
    "multi_all_finite": (lambda: [_f(3), _f(4)], {"num_arrays": 2}, None,
                         False),
})



# ---------------------------------------------------------------------------
# legacy scalar-op family (ops/legacy_elemwise.py) — numpy oracles
# ---------------------------------------------------------------------------
_S = 1.7
_SCALAR_TABLE = {
    "_plus_scalar": lambda x: x + _S,
    "_minus_scalar": lambda x: x - _S,
    "_rminus_scalar": lambda x: _S - x,
    "_mul_scalar": lambda x: x * _S,
    "_div_scalar": lambda x: x / _S,
    "_rdiv_scalar": lambda x: _S / x,
    "_mod_scalar": lambda x: onp.mod(x, _S),
    "_rmod_scalar": lambda x: onp.mod(_S, x),
    "_power_scalar": lambda x: onp.power(x, _S),
    "_rpower_scalar": lambda x: onp.power(_S, x),
    "_maximum_scalar": lambda x: onp.maximum(x, _S),
    "_minimum_scalar": lambda x: onp.minimum(x, _S),
    "_hypot_scalar": lambda x: onp.hypot(x, onp.float32(_S)),
    "_npi_copysign_scalar": lambda x: onp.copysign(x, _S),
    "_npi_rcopysign_scalar": lambda x: onp.copysign(onp.float32(_S), x),
    "_npi_arctan2_scalar": lambda x: onp.arctan2(x, onp.float32(_S)),
    "_npi_rarctan2_scalar": lambda x: onp.arctan2(onp.float32(_S), x),
    "_npi_fmax_scalar": lambda x: onp.fmax(x, _S),
    "_npi_fmin_scalar": lambda x: onp.fmin(x, _S),
    "_npi_fmod_scalar": lambda x: onp.fmod(x, _S),
    "_npi_rfmod_scalar": lambda x: onp.fmod(onp.float32(_S), x),
    "_npi_ldexp_scalar": lambda x: onp.ldexp(x, int(_S)),
    "_equal_scalar": lambda x: (x == _S).astype(x.dtype),
    "_not_equal_scalar": lambda x: (x != _S).astype(x.dtype),
    "_greater_scalar": lambda x: (x > _S).astype(x.dtype),
    "_greater_equal_scalar": lambda x: (x >= _S).astype(x.dtype),
    "_lesser_scalar": lambda x: (x < _S).astype(x.dtype),
    "_lesser_equal_scalar": lambda x: (x <= _S).astype(x.dtype),
    "_logical_and_scalar": lambda x: onp.logical_and(x, _S).astype(x.dtype),
    "_logical_or_scalar": lambda x: onp.logical_or(x, _S).astype(x.dtype),
    "_logical_xor_scalar": lambda x: onp.logical_xor(x, _S).astype(x.dtype),
}
_SCALAR_INT_TABLE = {
    "_npi_gcd_scalar": lambda x: onp.gcd(x, 2),
    "_npi_lcm_scalar": lambda x: onp.lcm(x, 2),
    "_npi_bitwise_and_scalar": lambda x: onp.bitwise_and(x, 2),
    "_npi_bitwise_or_scalar": lambda x: onp.bitwise_or(x, 2),
    "_npi_bitwise_xor_scalar": lambda x: onp.bitwise_xor(x, 2),
}


@pytest.mark.parametrize("name", sorted(_SCALAR_TABLE))
def test_scalar_op_forward(name):
    x = RNG.uniform(0.3, 2.5, size=(3, 4)).astype("float32")
    got = apply_op(name, NDArray(x), scalar=_S).asnumpy()
    assert_almost_equal(got.astype("float64"),
                        onp.asarray(_SCALAR_TABLE[name](x)).astype("float64"),
                        rtol=2e-3, atol=1e-5)


@pytest.mark.parametrize("name", sorted(_SCALAR_INT_TABLE))
def test_scalar_int_op_forward(name):
    x = RNG.randint(1, 6, size=(3, 4)).astype("int32")
    got = apply_op(name, NDArray(x), scalar=2).asnumpy()
    assert (got == _SCALAR_INT_TABLE[name](x)).all()


def test_npi_ldexp_rscalar():
    x = onp.array([1, 2, 3], dtype="float32")
    got = apply_op("_npi_rldexp_scalar", NDArray(x), scalar=1.5).asnumpy()
    assert_almost_equal(got, onp.ldexp(onp.float32(1.5), x.astype("int32")))


def test_where_scalar_variants():
    c = onp.array([True, False, True])
    r = onp.array([1.0, 2.0, 3.0], dtype="float32")
    assert_almost_equal(
        apply_op("_npi_where_lscalar", NDArray(c), NDArray(r), scalar=9.0),
        onp.where(c, 9.0, r))
    assert_almost_equal(
        apply_op("_npi_where_rscalar", NDArray(c), NDArray(r), scalar=9.0),
        onp.where(c, r, 9.0))
    assert_almost_equal(
        apply_op("_npi_where_scalar2", NDArray(c), x=1.0, y=-1.0),
        onp.where(c, 1.0, -1.0))


def test_grad_through_scalar_and_identity_ops():
    x = NDArray(onp.array([1.0, -2.0, 3.0], dtype="float32"))
    check_numeric_gradient(
        lambda ins: apply_op("_mul_scalar", ins[0], scalar=2.5).sum(), [x])
    check_numeric_gradient(
        lambda ins: apply_op("_rdiv_scalar", ins[0], scalar=2.0).sum(),
        [NDArray(onp.array([1.0, 2.0, 4.0], dtype="float32"))])
    # make_loss backward = grad_scale regardless of head gradient
    import mxnet_tpu as _mx
    y = NDArray(onp.array([1.0, 2.0], dtype="float32"))
    y.attach_grad()
    with _mx.autograd.record():
        z = (apply_op("make_loss", y, grad_scale=3.0) * 5.0).sum()
    z.backward()
    assert_almost_equal(y.grad, [3.0, 3.0])
    # gradientmultiplier scales (and can reverse) the gradient
    w = NDArray(onp.array([1.0, 2.0], dtype="float32"))
    w.attach_grad()
    with _mx.autograd.record():
        z = (apply_op("gradientmultiplier", w, scalar=-1.0) * 2.0).sum()
    z.backward()
    assert_almost_equal(w.grad, [-2.0, -2.0])


SPECS.update({
    # unary extras
    "reciprocal_sqrt": (lambda: [onp.abs(_f(3, 4)) + 0.2], {},
                        lambda x: 1.0 / onp.sqrt(x), True),
    "rcbrt": (lambda: [onp.abs(_f(3, 4)) + 0.2], {},
              lambda x: 1.0 / onp.cbrt(x), True),
    "digamma": (lambda: [onp.abs(_f(3, 4)) + 0.5], {}, None, True),
    "hard_sigmoid": (lambda: [_f(3, 4) * 5], {},
                     lambda x: onp.clip(0.2 * x + 0.5, 0, 1), False),
    "nanprod": (lambda: [_f(3, 4)], {"axis": 1},
                lambda x: onp.nanprod(x, 1), False),
    "ones_like": (lambda: [_f(3, 4)], {}, lambda x: onp.ones_like(x), False),
    "zeros_like": (lambda: [_f(3, 4)], {}, lambda x: onp.zeros_like(x),
                   False),
    "make_loss": (lambda: [_f(3, 4)], {}, lambda x: x, False),
    "gradientmultiplier": (lambda: [_f(3, 4)], {"scalar": 2.0},
                           lambda x: x, False),
    "IdentityAttachKLSparseReg": (lambda: [onp.abs(_f(3, 4))], {},
                                  lambda x: x, False),
    "_grad_add": (lambda: [_f(3, 4), _f(3, 4)], {},
                  lambda a, b: a + b, True),
    "add_n": (lambda: [_f(3, 4), _f(3, 4), _f(3, 4)], {},
              lambda a, b, c: a + b + c, True),
    "_identity_with_attr_like_rhs": (lambda: [_f(3, 4), _f(3, 4)], {},
                                     lambda a, b: a, False),
    "_npx_constraint_check": (lambda: [onp.array([True, True])],
                              {"msg": "ok"},
                              lambda x: onp.array(True), False),
    "div_sqrt_dim": (lambda: [_f(3, 16)], {},
                     lambda x: x / onp.sqrt(16.0), True),
    # creation
    "zeros": (lambda: [], {"shape": (2, 3)},
              lambda: onp.zeros((2, 3), "float32"), False),
    "ones": (lambda: [], {"shape": (2, 3)},
             lambda: onp.ones((2, 3), "float32"), False),
    "full": (lambda: [], {"shape": (2, 3), "value": 7.0},
             lambda: onp.full((2, 3), 7.0, "float32"), False),
    "full_like": (lambda: [_f(2, 3)], {"fill_value": 2.5},
                  lambda x: onp.full_like(x, 2.5), False),
    "eye": (lambda: [], {"N": 3, "k": 1},
            lambda: onp.eye(3, k=1, dtype="float32"), False),
    # bare `identity` is an alias of `copy` (elemwise_unary_op_basic.cc:245);
    # the matrix creator lives only at _npi_identity (np_init_op.cc)
    "identity": (lambda: [_f(2, 3)], {},
                 lambda x: x, False),
    "_npi_identity": (lambda: [], {"n": 3},
                      lambda: onp.identity(3, "float32"), False),
    "arange": (lambda: [], {"start": 2, "stop": 8, "step": 2,
                            "dtype": "float32"},
               lambda: onp.arange(2, 8, 2, "float32"), False),
    "linspace": (lambda: [], {"start": 0.0, "stop": 1.0, "num": 5},
                 lambda: onp.linspace(0, 1, 5, dtype="float32"), False),
    "logspace": (lambda: [], {"start": 0.0, "stop": 2.0, "num": 3},
                 lambda: onp.logspace(0, 2, 3, dtype="float32"), False),
    "tri": (lambda: [], {"N": 3, "k": 0},
            lambda: onp.tri(3, dtype="float32"), False),
    "indices": (lambda: [], {"dimensions": (2, 3)},
                lambda: onp.indices((2, 3)), False),
    # stack/split variants
    "hstack": (lambda: [_f(2, 3), _f(2, 3)], {},
               lambda a, b: onp.hstack([a, b]), True),
    "vstack": (lambda: [_f(2, 3), _f(2, 3)], {},
               lambda a, b: onp.vstack([a, b]), True),
    "dstack": (lambda: [_f(2, 3), _f(2, 3)], {},
               lambda a, b: onp.dstack([a, b]), True),
    "column_stack": (lambda: [_f(3), _f(3)], {},
                     lambda a, b: onp.column_stack([a, b]), True),
    "hsplit": (lambda: [_f(2, 4)], {"indices_or_sections": 2},
               lambda x: onp.hsplit(x, 2)[0], False),
    "dsplit": (lambda: [_f(2, 3, 4)], {"indices_or_sections": 2},
               lambda x: onp.dsplit(x, 2)[0], False),
    # legacy slice family
    "slice": (lambda: [_f(4, 5)], {"begin": (1, 0), "end": (3, 4)},
              lambda x: x[1:3, 0:4], True),
    "slice_axis": (lambda: [_f(4, 5)], {"axis": 1, "begin": 1, "end": 4},
                   lambda x: x[:, 1:4], True),
    "slice_like": (lambda: [_f(4, 5), _f(2, 3)], {},
                   lambda x, y: x[:2, :3], True),
    "broadcast_axis": (lambda: [_f(1, 4)], {"axis": 0, "size": 3},
                       lambda x: onp.broadcast_to(x, (3, 4)), True),
    "broadcast_like": (lambda: [_f(1, 4), _f(3, 4)], {},
                       lambda x, y: onp.broadcast_to(x, (3, 4)), True),
    "reshape_like": (lambda: [_f(2, 6), _f(3, 4)], {},
                     lambda x, y: x.reshape(3, 4), True),
    "Reshape": (lambda: [_f(3, 4)], {"shape": (-1, 0)},
                lambda x: x.reshape(3, 4), True),
    "_npx_reshape": (lambda: [_f(3, 4)], {"newshape": (-2, -1)},
                     lambda x: x.reshape(3, 4), True),
    "SliceChannel": (lambda: [_f(4, 6)], {"num_outputs": 2, "axis": 1},
                     lambda x: onp.split(x, 2, 1)[0], False),
    "_split_v2": (lambda: [_f(4, 6)], {"sections": 3, "axis": 1},
                  lambda x: onp.split(x, 3, 1)[0], False),
    "swapaxes_legacy": (lambda: [_f(3, 4, 2)], {"dim1": 0, "dim2": 2},
                        lambda x: x.swapaxes(0, 2), True),
    "_rnn_param_concat": (lambda: [_f(2, 3), _f(4)], {},
                          lambda a, b: onp.concatenate(
                              [a.ravel(), b.ravel()]), False),
    # scatter / assignment
    "scatter_nd": (lambda: [_f(2), onp.array([[0, 1], [1, 2]])],
                   {"shape": (3, 4)}, None, False),
    "_scatter_set_nd": (lambda: [_f(2), onp.array([[0, 1], [1, 2]])],
                        {"shape": (3, 4)}, None, False),
    "_slice_assign": (lambda: [_f(4, 5), _f(2, 5)],
                      {"begin": (1,), "end": (3,)}, None, False),
    "_slice_assign_scalar": (lambda: [_f(4, 5)],
                             {"begin": (1,), "end": (3,), "scalar": 9.0},
                             None, False),
    # sparse-storage helpers
    "cast_storage": (lambda: [_f(3, 4)], {"stype": "default"},
                     lambda x: x, False),
    "_sparse_retain": (lambda: [_f(5, 3), onp.array([1, 3])], {}, None,
                       False),
    "square_sum": (lambda: [_f(3, 4)], {"axis": 1},
                   lambda x: (x * x).sum(1), True),
    # multi-tensor helpers
    "multi_sum_sq": (lambda: [_f(3), _f(4)], {"num_arrays": 2},
                     lambda a, b: (a * a).sum(), False),
    "reset_arrays": (lambda: [_f(3), _f(4)], {"num_arrays": 2},
                     lambda a, b: onp.zeros(3, "float32"), False),
    "multi_lars": (lambda: [onp.full(3, 0.1, "float32"),
                            onp.full(3, 4.0, "float32"),
                            onp.full(3, 1.0, "float32"),
                            onp.zeros(3, "float32")],
                   {"eta": 1.0, "eps": 0.0},
                   lambda lr, w, g, wd: lr * onp.sqrt(w) / onp.sqrt(g),
                   False),
    "histogram": (lambda: [_f(32)], {"bin_cnt": 4, "range": (-1, 1)},
                  None, False),
    # contrib misc
    "index_array": (lambda: [_f(2, 3)], {}, None, False),
    "_npi_share_memory": (lambda: [_f(2), _f(2)], {},
                          lambda a, b: onp.array(False), False),
    "_npi_diag_indices_from": (lambda: [_f(3, 3)], {},
                               lambda x: onp.diag_indices_from(x)[0], False),
    "_contrib_dynamic_reshape": (lambda: [_f(3, 4), onp.array([4, 3])],
                                 {}, lambda x, s: x.reshape(4, 3), False),
    # legacy NN extras
    "lrn": (lambda: [onp.abs(_f(1, 8, 2, 2)) + 0.1], {"nsize": 5}, None,
            True),
    "softmax_activation": (lambda: [_f(2, 5)], {"mode": "instance"},
                           None, True),
    "batch_norm_with_relu": (
        lambda: [_f(2, 3, 4, 4), onp.ones(3, "float32"),
                 onp.zeros(3, "float32"), onp.zeros(3, "float32"),
                 onp.ones(3, "float32")], {}, None, False),
    "sync_batch_norm": (
        lambda: [_f(2, 3, 4, 4), onp.ones(3, "float32"),
                 onp.zeros(3, "float32"), onp.zeros(3, "float32"),
                 onp.ones(3, "float32")], {}, None, False),
})


_R1 = (lambda: [onp.array(1.0, "float32")])
SPECS.update({
    # mixed-precision single-tensor updates (ops/optimizer_ops.py)
    "mp_sgd_update": (lambda: [_f(4), _f(4), _f(4)], {"lr": 0.1},
                      None, False),
    "mp_sgd_mom_update": (lambda: [_f(4), _f(4), _f(4), _f(4)],
                          {"lr": 0.1}, None, False),
    "mp_nag_mom_update": (lambda: [_f(4), _f(4), _f(4), _f(4)],
                          {"lr": 0.1}, None, False),
    "mp_lamb_update_phase1": (lambda: [_f(4), _f(4), _f(4),
                                       onp.abs(_f(4)), _f(4)],
                              {"t": 1}, None, False),
    "mp_lamb_update_phase2": (lambda: [_f(4), _f(4), onp.array([1.0]),
                                       onp.array([1.0]), _f(4)],
                              {"lr": 0.01}, None, False),
    "mp_adamw_update": (lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4)),
                                 _f(4), onp.array(1.0, "float32")],
                        {"lr": 0.01}, None, False),
    "mp_adabelief_update": (lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4)),
                                     _f(4), onp.array(1.0, "float32")],
                            {"lr": 0.01}, None, False),
    # multi-tensor updates — interleaved reference operand layout
    "multi_sgd_mom_update": (lambda: [_f(3), _f(3), _f(3),
                                      _f(4), _f(4), _f(4)],
                             {"lrs": (0.1, 0.1), "wds": (0.0, 0.0),
                              "num_weights": 2}, None, False),
    "multi_mp_sgd_update": (lambda: [_f(3), _f(3), _f(3)],
                            {"lrs": (0.1,), "wds": (0.0,),
                             "num_weights": 1}, None, False),
    "multi_mp_sgd_mom_update": (lambda: [_f(3), _f(3), _f(3), _f(3)],
                                {"lrs": (0.1,), "wds": (0.0,),
                                 "num_weights": 1}, None, False),
    "preloaded_multi_sgd_update": (
        lambda: [_f(3), _f(3), onp.array([0.1], "float32"),
                 onp.array([0.0], "float32")],
        {"num_weights": 1}, None, False),
    "preloaded_multi_sgd_mom_update": (
        lambda: [_f(3), _f(3), _f(3), onp.array([0.1], "float32"),
                 onp.array([0.0], "float32")],
        {"num_weights": 1}, None, False),
    "preloaded_multi_mp_sgd_update": (
        lambda: [_f(3), _f(3), _f(3), onp.array([0.1], "float32"),
                 onp.array([0.0], "float32")],
        {"num_weights": 1}, None, False),
    "preloaded_multi_mp_sgd_mom_update": (
        lambda: [_f(3), _f(3), _f(3), _f(3), onp.array([0.1], "float32"),
                 onp.array([0.0], "float32")],
        {"num_weights": 1}, None, False),
    "multi_adamw_update": (
        lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4)),
                 onp.array(1.0, "float32")],
        {"lrs": (0.01,), "wds": (0.01,), "etas": (1.0,),
         "num_weights": 1}, None, False),
    "multi_mp_adamw_update": (
        lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4)), _f(4),
                 onp.array(1.0, "float32")],
        {"lrs": (0.01,), "wds": (0.01,), "etas": (1.0,),
         "num_weights": 1}, None, False),
    "multi_lamb_update": (
        lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4))],
        {"learning_rates": (0.01,), "wds": (0.0,), "step_count": (1,),
         "num_tensors": 1}, None, False),
    "multi_mp_lamb_update": (
        lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4)), _f(4)],
        {"learning_rates": (0.01,), "wds": (0.0,), "step_count": (1,),
         "num_tensors": 1}, None, False),
    "multi_lans_update": (
        lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4))],
        {"learning_rates": (0.01,), "wds": (0.0,), "step_count": (1,),
         "num_tensors": 1}, None, False),
    "multi_mp_lans_update": (
        lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4)), _f(4)],
        {"learning_rates": (0.01,), "wds": (0.0,), "step_count": (1,),
         "num_tensors": 1}, None, False),
    "multi_adabelief_update": (
        lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4)),
                 onp.array(1.0, "float32")],
        {"lrs": (0.01,), "wds": (0.0,), "etas": (1.0,),
         "num_weights": 1}, None, False),
    "multi_mp_adabelief_update": (
        lambda: [_f(4), _f(4), _f(4), onp.abs(_f(4)), _f(4),
                 onp.array(1.0, "float32")],
        {"lrs": (0.01,), "wds": (0.0,), "etas": (1.0,),
         "num_weights": 1}, None, False),
})


def test_mp_sgd_matches_fp32_master():
    """mp update must track the fp32 master, not the low-precision weight."""
    w32 = onp.linspace(-1, 1, 8).astype("float32")
    w16 = w32.astype("float16")
    g = onp.full(8, 0.5, "float32")
    w_out, w32_out = apply_op("mp_sgd_update", NDArray(w16),
                              NDArray(g.astype("float16")), NDArray(w32),
                              lr=0.1)
    assert_almost_equal(w32_out, w32 - 0.1 * 0.5, rtol=1e-6)
    assert str(w_out.dtype) == "float16"


# ---------------------------------------------------------------------------
# random sampler ops (ops/random_ops.py): each draws N samples and checks
# the first two moments against the analytic distribution
# (reference pattern: tests/python/unittest/test_random.py)
# ---------------------------------------------------------------------------
_N = 4000
# name -> (attrs, expected_mean, expected_std, tol)
_SAMPLER_SPECS = {
    "_random_uniform": ({"low": 2.0, "high": 4.0, "shape": (_N,)},
                        3.0, 2.0 / 12 ** 0.5, 0.1),
    "_random_normal": ({"loc": 1.0, "scale": 2.0, "shape": (_N,)},
                       1.0, 2.0, 0.15),
    "_random_gamma": ({"alpha": 2.0, "beta": 3.0, "shape": (_N,)},
                      6.0, 18 ** 0.5, 0.3),
    "_random_exponential": ({"lam": 2.0, "shape": (_N,)}, 0.5, 0.5, 0.05),
    "_random_poisson": ({"lam": 4.0, "shape": (_N,)}, 4.0, 2.0, 0.2),
    "_random_negative_binomial": ({"k": 3, "p": 0.5, "shape": (_N,)},
                                  3.0, 6 ** 0.5, 0.25),
    "_random_generalized_negative_binomial":
        ({"mu": 2.0, "alpha": 0.5, "shape": (_N,)},
         2.0, (2.0 + 0.5 * 4.0) ** 0.5, 0.25),
    "_npi_uniform": ({"low": 0.0, "high": 1.0, "size": (_N,)},
                     0.5, 1 / 12 ** 0.5, 0.05),
    "_npi_normal": ({"loc": 0.0, "scale": 1.0, "size": (_N,)},
                    0.0, 1.0, 0.08),
    "_npi_exponential": ({"scale": 2.0, "size": (_N,)}, 2.0, 2.0, 0.2),
    "_npi_gumbel": ({"loc": 0.0, "scale": 1.0, "size": (_N,)},
                    0.5772, 3.14159 / 6 ** 0.5, 0.12),
    "_npi_laplace": ({"loc": 0.0, "scale": 1.0, "size": (_N,)},
                     0.0, 2 ** 0.5, 0.12),
    "_npi_logistic": ({"loc": 0.0, "scale": 1.0, "size": (_N,)},
                      0.0, 3.14159 / 3 ** 0.5, 0.15),
    "_npi_pareto": ({"a": 3.0, "size": (_N,)}, 0.5, 0.75 ** 0.5, 0.2),
    "_npi_rayleigh": ({"scale": 2.0, "size": (_N,)},
                      2.0 * (3.14159 / 2) ** 0.5, None, 0.15),
    "_npi_weibull": ({"a": 2.0, "size": (_N,)}, 0.8862, None, 0.1),
    "_npi_gamma": ({"shape": 2.0, "scale": 3.0, "size": (_N,)},
                   6.0, 18 ** 0.5, 0.3),
}


@pytest.mark.parametrize("name", sorted(_SAMPLER_SPECS))
def test_sampler_moments(name):
    import mxnet_tpu as _mx

    _mx.random.seed(zlib_seed(name))
    attrs, mean, std, tol = _SAMPLER_SPECS[name]
    draws = apply_op(name, **attrs).asnumpy().astype("float64")
    assert abs(draws.mean() - mean) < 4 * tol, (draws.mean(), mean)
    if std is not None:
        assert abs(draws.std() - std) < 6 * tol, (draws.std(), std)


def test_sampler_bernoulli_and_randint():
    import mxnet_tpu as _mx

    _mx.random.seed(11)
    b = apply_op("_npi_bernoulli", prob=0.3, size=(_N,)).asnumpy()
    assert abs(b.mean() - 0.3) < 0.05 and set(onp.unique(b)) <= {0.0, 1.0}
    r = apply_op("_random_randint", low=2, high=7,
                 shape=(_N,)).asnumpy()
    assert r.min() >= 2 and r.max() <= 6


def test_sampler_rowwise_and_choice():
    import mxnet_tpu as _mx

    _mx.random.seed(13)
    lo = NDArray(onp.array([0.0, 10.0], dtype="float32"))
    hi = NDArray(onp.array([1.0, 20.0], dtype="float32"))
    u = apply_op("_sample_uniform", lo, hi, shape=(500,)).asnumpy()
    assert u.shape == (2, 500)
    assert abs(u[0].mean() - 0.5) < 0.1 and abs(u[1].mean() - 15.0) < 1.0
    n = apply_op("_sample_normal", lo, hi, shape=(500,)).asnumpy()
    assert abs(n[0].mean()) < 0.2
    g = apply_op("_sample_gamma",
                 NDArray(onp.array([2.0], dtype="float32")),
                 NDArray(onp.array([3.0], dtype="float32")),
                 shape=(2000,)).asnumpy()
    assert abs(g.mean() - 6.0) < 0.8
    e = apply_op("_sample_exponential",
                 NDArray(onp.array([2.0], dtype="float32")),
                 shape=(2000,)).asnumpy()
    assert abs(e.mean() - 0.5) < 0.1
    p = apply_op("_sample_poisson",
                 NDArray(onp.array([4.0], dtype="float32")),
                 shape=(2000,)).asnumpy()
    assert abs(p.mean() - 4.0) < 0.4
    nb = apply_op("_sample_negative_binomial",
                  NDArray(onp.array([3.0], dtype="float32")),
                  NDArray(onp.array([0.5], dtype="float32")),
                  shape=(2000,)).asnumpy()
    assert abs(nb.mean() - 3.0) < 0.6
    gnb = apply_op("_sample_generalized_negative_binomial",
                   NDArray(onp.array([2.0], dtype="float32")),
                   NDArray(onp.array([0.5], dtype="float32")),
                   shape=(2000,)).asnumpy()
    assert abs(gnb.mean() - 2.0) < 0.6
    c = apply_op("_npi_choice", a=5, size=(300,)).asnumpy()
    assert c.min() >= 0 and c.max() <= 4
    m = apply_op("_sample_multinomial",
                 NDArray(onp.array([[0.1, 0.9], [0.9, 0.1]],
                                   dtype="float32")),
                 shape=(500,)).asnumpy()
    assert m.shape == (2, 500)
    assert m[0].mean() > 0.8 and m[1].mean() < 0.2
    o, lp = apply_op("_sample_multinomial",
                     NDArray(onp.array([0.5, 0.5], dtype="float32")),
                     shape=(4,), get_prob=True)
    assert_almost_equal(lp, onp.full(4, onp.log(0.5)), rtol=1e-5)
    nn = apply_op("_npi_normal_n",
                  NDArray(onp.array([0.0, 5.0], dtype="float32")),
                  NDArray(onp.array([1.0, 1.0], dtype="float32")),
                  size=(400,)).asnumpy()
    assert nn.shape == (400, 2) and abs(nn[:, 1].mean() - 5.0) < 0.3
    un = apply_op("_npi_uniform_n",
                  NDArray(onp.array([0.0], dtype="float32")),
                  NDArray(onp.array([2.0], dtype="float32")),
                  size=(400,)).asnumpy()
    assert abs(un.mean() - 1.0) < 0.2
    s = apply_op("_shuffle",
                 NDArray(onp.arange(8, dtype="float32"))).asnumpy()
    assert sorted(s.tolist()) == list(range(8))
    # numpy multinomial: per-category COUNTS, shape size+(ncat,), sums to n
    cnt = apply_op("_npi_multinomial",
                   NDArray(onp.array([0.2, 0.8], dtype="float32")),
                   n=100, size=(50,)).asnumpy()
    assert cnt.shape == (50, 2)
    assert (cnt.sum(axis=-1) == 100).all()
    assert abs(cnt[:, 1].mean() - 80.0) < 5.0
    cnt2 = apply_op("_npi_multinomial", n=10,
                    pvals=(0.5, 0.5)).asnumpy()
    assert cnt2.shape == (2,) and cnt2.sum() == 10


_SAMPLER_COVERED = set(_SAMPLER_SPECS) | {
    "_npi_bernoulli", "_random_randint", "_sample_uniform",
    "_sample_normal", "_sample_gamma", "_sample_exponential",
    "_sample_poisson", "_sample_negative_binomial",
    "_sample_generalized_negative_binomial", "_sample_multinomial",
    "_npi_multinomial",
    "_npi_choice", "_npi_normal_n", "_npi_uniform_n", "_shuffle",
}


# ops proven in dedicated test files (sweep exemption must name the file)
COVERED_ELSEWHERE = {
    "batch_norm": "test_operator_nn.py",
    "dropout": "test_operator_nn.py (rng op)",
    "ctc_loss": "test_operator_nn.py",
    "rnn": "test_rnn.py",
    "multihead_attention": "test_attention_models.py",
    "flash_attention": "test_attention_models.py",
    "paged_decode_attention": "test_decode.py (kernel and plain body)",
    "mla_decode_attention": "test_axk1.py (kernel and plain body)",
    # the Qwen3-Next operators: values and gradients against the plain
    # reference (recurrence, dense experts)
    "rope": "test_qwen3_next.py",
    "causal_conv1d": "test_qwen3_next.py",
    "causal_conv1d_state": "test_granite_hybrid.py",
    "ssd_chunk_scan": "test_granite_hybrid.py",
    "ssd_step": "test_granite_hybrid.py",
    "gated_delta_rule": "test_qwen3_next.py",
    "moe_router": "test_qwen3_next.py",
    "routed_experts": "test_qwen3_next.py",
    "box_nms": "test_vision_ops.py",
    "dot_csr": "test_aux_modules.py (device CSR dot)",
    "box_encode": "test_vision_ops.py",
    # spatial-warping / deformable tier — forward+grad oracles
    "bilinear_sampler": "test_warp_ops.py",
    "grid_generator": "test_warp_ops.py",
    "spatial_transformer": "test_warp_ops.py",
    "correlation": "test_warp_ops.py",
    "deformable_convolution": "test_warp_ops.py",
    "modulated_deformable_convolution": "test_warp_ops.py",
    "psroi_pooling": "test_warp_ops.py",
    "deformable_psroi_pooling": "test_warp_ops.py",
    "contrib_quantize": "test_contrib.py",
    "quantized_fully_connected": "test_contrib.py",
    "contrib_dequantize": "test_contrib.py",
    "matmul": "test_numpy_op.py",
    "slice_key": "test_op_sweep.py::test_indexing_ops_via_public_api",
    "index_update": "test_op_sweep.py::test_indexing_ops_via_public_api",
    "index_add": "test_op_sweep.py::test_indexing_ops_via_public_api",
    "dot": "test_numpy_op.py",
    "true_divmod": "test_numpy_op.py",
    # megatron tp collectives — identity outside a TPContext; the sharded
    # fwd/bwd semantics need a dp x tp mesh and are driven in test_tp.py
    "tp_copy": "test_tp.py (megatron f: identity fwd / psum bwd)",
    "tp_sum": "test_tp.py (megatron g: psum fwd / identity bwd)",
    "tp_gather": "test_tp.py (tiled all_gather fwd / slice-own bwd)",
    "linalg_inv": "test_numpy_op.py (linalg)",
    "linalg_pinv": "test_numpy_op.py (linalg)",
    "linalg_det": "test_numpy_op.py (linalg)",
    "linalg_cholesky": "test_numpy_op.py (linalg)",
    "linalg_eigh": "test_numpy_op.py (linalg)",
    "linalg_eigvalsh": "test_numpy_op.py (linalg)",
    "linalg_matrix_rank": "test_numpy_op.py (linalg)",
    # int8 quantized family — dequantize-vs-fp32 oracles
    "quantize_v2": "test_quantized_ops.py",
    "requantize": "test_quantized_ops.py",
    "quantized_act": "test_quantized_ops.py",
    "quantized_flatten": "test_quantized_ops.py",
    "quantized_concat": "test_quantized_ops.py",
    "quantized_elemwise_add": "test_quantized_ops.py",
    "quantized_elemwise_mul": "test_quantized_ops.py",
    "quantized_embedding": "test_quantized_ops.py",
    "quantized_fully_connected_v2": "test_quantized_ops.py",
    "quantized_conv": "test_quantized_ops.py",
    "quantized_pooling": "test_quantized_ops.py",
    "quantized_batch_norm": "test_quantized_ops.py",
    "round_ste": "test_quantized_ops.py",
    "sign_ste": "test_quantized_ops.py",
    "intgemm_maxabsolute": "test_quantized_ops.py",
    "intgemm_prepare_data": "test_quantized_ops.py",
    "intgemm_prepare_weight": "test_quantized_ops.py",
    "intgemm_take_weight": "test_quantized_ops.py",
    "intgemm_fully_connected": "test_quantized_ops.py",
    # sldwin attention / dgl graph / image-cv tiers
    "sldwin_atten_score": "test_graph_image_ops.py",
    "sldwin_atten_context": "test_graph_image_ops.py",
    "sldwin_atten_mask_like": "test_graph_image_ops.py",
    "dgl_adjacency": "test_graph_image_ops.py",
    "dgl_subgraph": "test_graph_image_ops.py",
    "dgl_csr_neighbor_uniform_sample": "test_graph_image_ops.py",
    "dgl_csr_neighbor_non_uniform_sample": "test_graph_image_ops.py",
    "dgl_graph_compact": "test_graph_image_ops.py",
    "edge_id": "test_graph_image_ops.py",
    "getnnz": "test_graph_image_ops.py",
    "image_to_tensor": "test_graph_image_ops.py",
    "image_normalize": "test_graph_image_ops.py",
    "image_resize": "test_graph_image_ops.py",
    "image_crop": "test_graph_image_ops.py",
    "image_random_crop": "test_graph_image_ops.py",
    "image_random_resized_crop": "test_graph_image_ops.py",
    "cvimresize": "test_graph_image_ops.py",
    "cvcopyMakeBorder": "test_graph_image_ops.py",
    "cvimdecode": "test_graph_image_ops.py",
    "cvimread": "test_graph_image_ops.py",
    # dynamic-shape manip / control flow / contrib stragglers
    "unique": "test_npi_manip_ops.py",
    "nonzero": "test_npi_manip_ops.py",
    "boolean_mask": "test_npi_manip_ops.py",
    "_npi_boolean_mask_assign_scalar": "test_npi_manip_ops.py",
    "_npi_boolean_mask_assign_tensor": "test_npi_manip_ops.py",
    "delete": "test_npi_manip_ops.py",
    "_npi_insert_scalar": "test_npi_manip_ops.py",
    "_npi_insert_slice": "test_npi_manip_ops.py",
    "_npi_insert_tensor": "test_npi_manip_ops.py",
    "advanced_indexing": "test_npi_manip_ops.py",
    "advanced_indexing_multiple": "test_npi_manip_ops.py",
    "Concat": "test_npi_manip_ops.py",
    "_foreach": "test_npi_manip_ops.py (+ test_control_flow.py)",
    "_while_loop": "test_npi_manip_ops.py (+ test_control_flow.py)",
    "_cond": "test_npi_manip_ops.py (+ test_control_flow.py)",
    "hawkesll": "test_npi_manip_ops.py",
    "mrcnn_mask_target": "test_npi_manip_ops.py",
    "rroi_align": "test_npi_manip_ops.py",
    "calibrate_entropy": "test_npi_manip_ops.py",
    "Custom": "test_npi_manip_ops.py (+ test_aux_modules.py)",
}


def test_registry_fully_covered():
    """EVERY registered op is swept here, in a table sweep, or explicitly
    mapped to its dedicated test file. A name registered via register_alias
    (Op.name != key) is covered by its target's coverage — the alias shares
    the implementation, so one sweep proves both names."""
    table = (set(_UNARY_NAMES) | set(_BINARY_NAMES) | set(_SCALAR_TABLE)
             | set(_SCALAR_INT_TABLE) | _SAMPLER_COVERED
             | {"_npi_rldexp_scalar", "_npi_where_lscalar",
                "_npi_where_rscalar", "_npi_where_scalar2"})
    covered = table | set(SPECS) | set(COVERED_ELSEWHERE)
    missing = []
    for name, op in _OPS.items():
        if name.startswith("_test_"):
            continue
        if name in covered:
            continue
        if op.name != name and op.name in covered:
            continue  # alias of a covered op
        missing.append(name)
    assert not missing, (
        f"ops with no sweep coverage: {sorted(missing)} — add a SPECS entry "
        "or map them in COVERED_ELSEWHERE")


@pytest.mark.parametrize("name", sorted(SPECS))
def test_spec_forward(name):
    build, attrs, oracle, _ = SPECS[name]
    _reseed(name)
    ins = build()
    outs = apply_op(name, *[NDArray(x) for x in ins], **attrs)
    first = outs[0] if isinstance(outs, (tuple, list)) else outs
    got = first.asnumpy()
    assert got.size >= 0  # materialized without error
    if oracle is not None:
        want = onp.asarray(oracle(*ins))
        if onp.iscomplexobj(want):
            assert_almost_equal(onp.abs(got), onp.abs(want), rtol=2e-3,
                                atol=1e-4)
        else:
            assert_almost_equal(got.astype("float64"),
                                want.astype("float64"), rtol=2e-3,
                                atol=1e-4)


_GRAD_SPECS = sorted(n for n, s in SPECS.items() if s[3])


@pytest.mark.parametrize("name", _GRAD_SPECS)
def test_spec_numeric_gradient(name):
    build, attrs, _, _ = SPECS[name]
    _reseed(name)
    ins = [NDArray(x) for x in build()]

    def loss(xs):
        out = apply_op(name, *xs, **attrs)
        if isinstance(out, (tuple, list)):
            out = out[0]
        return (out * out).sum()

    check_numeric_gradient(loss, ins)


def test_indexing_ops_via_public_api():
    """slice_key / index_update / index_add through their public entry
    points (NDArray __getitem__/__setitem__, npx.index_update/add)."""
    from mxnet_tpu import np as mnp
    from mxnet_tpu.ops import indexing as ix

    x = mnp.array(RNG.rand(4, 5).astype("float32"))
    ref = onp.array(x.asnumpy())  # asnumpy may return a read-only view
    # advanced indexing → slice_key op
    got = x[1:3, [0, 2]].asnumpy()
    assert_almost_equal(got, ref[1:3, [0, 2]], rtol=1e-6)
    # index_update via setitem
    ix.setitem(x, (slice(0, 2), 1), mx.np.ones((2,)))
    ref[0:2, 1] = 1.0
    assert_almost_equal(x.asnumpy(), ref, rtol=1e-6)
    # index_add
    y = ix.index_add_api(x, (slice(None), 0), mnp.ones((4,))) \
        if hasattr(ix, "index_add_api") else None
    if y is None:
        from mxnet_tpu.ops.indexing import _freeze_key
        from mxnet_tpu.ops.registry import get_op, invoke
        spec, arrays = _freeze_key((slice(None), 0))
        y = invoke(get_op("index_add"), [x, mnp.ones((4,))] + arrays,
                   {"spec": spec})
    ref[:, 0] += 1.0
    assert_almost_equal(y.asnumpy(), ref, rtol=1e-6)


def test_sparse_adagrad_only_touches_active_rows():
    """Reference row-sparse semantics (optimizer_op.cc sparse adagrad):
    rows outside the gradient's index set must be bit-identical."""
    w = _f(6, 3)
    h = onp.abs(_f(6, 3))
    g = _f(2, 3)
    idx = onp.array([1, 4])
    new_w, new_h = apply_op("sparse_adagrad_update", NDArray(w), NDArray(h),
                            NDArray(g), NDArray(idx), lr=0.1)
    nw, nh = new_w.asnumpy(), new_h.asnumpy()
    untouched = [0, 2, 3, 5]
    assert (nw[untouched] == w[untouched]).all()
    assert (nh[untouched] == h[untouched]).all()
    assert not (nw[[1, 4]] == w[[1, 4]]).all()
    # touched-row math matches dense adagrad on those rows
    hr = h[[1, 4]] + g * g
    wr = w[[1, 4]] - 0.1 * g / (onp.sqrt(hr) + 1e-7)
    assert_almost_equal(nw[[1, 4]], wr, rtol=1e-5, atol=1e-6)


def test_adam_update_op_matches_reference_formula():
    """adam_update implements the reference's UNCORRECTED update
    (optimizer_op.cc adam_update has no bias correction — the python
    Optimizer layer applies it via rescaled lr)."""
    w = _f(5)
    g = _f(5)
    mean0 = onp.zeros(5, "float32")
    var0 = onp.zeros(5, "float32")
    new_w, m, v = apply_op("adam_update", NDArray(w), NDArray(g),
                           NDArray(mean0), NDArray(var0), lr=0.01)
    m_ref = 0.1 * g
    v_ref = 0.001 * g * g
    w_ref = w - 0.01 * m_ref / (onp.sqrt(v_ref) + 1e-8)
    assert_almost_equal(new_w.asnumpy(), w_ref, rtol=1e-5, atol=1e-6)
    assert_almost_equal(m.asnumpy(), m_ref, rtol=1e-5, atol=1e-7)
    assert_almost_equal(v.asnumpy(), v_ref, rtol=1e-5, atol=1e-8)
