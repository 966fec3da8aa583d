"""Test fixtures (reference: conftest.py — seed fixture :75-97,
module_scope_waitall :61).

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware (the driver separately dry-runs them).
"""
import os
import tempfile

# the suite runs on the CPU backend with eight virtual devices (Pallas
# kernels, where a test turns them on, in interpret mode), whatever the
# machine has; the chip is exercised by chip_smoke.py. Subprocesses that
# tests spawn inherit the variable.
os.environ["JAX_PLATFORMS"] = "cpu"
# the trainer, Predictor and DecodeEngine persist what they compile; the
# suite places that cache outside the checkout (a full run writes ~200 MB)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(tempfile.gettempdir(),
                                   "mxtpu_tests_jax_cache"))
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as onp  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def seed_everything(request):
    """Reproducible seeds per test, logged on failure (reference pattern)."""
    seed = onp.random.randint(0, 2 ** 31)
    marker = request.node.get_closest_marker("seed")
    if marker is not None:
        seed = marker.args[0]
    onp.random.seed(seed)
    import mxnet_tpu as mx

    mx.random.seed(seed)
    yield seed


@pytest.fixture(scope="module", autouse=True)
def module_scope_waitall():
    yield
    import mxnet_tpu as mx

    mx.waitall()


@pytest.fixture
def paged_kernel_interpreted(monkeypatch):
    """While the test runs, ``npx.paged_decode_attention`` takes its Pallas
    kernel, interpreted, wherever it is traced (pages of 128 positions
    permitting). Only this op is steered (its body imports
    ``pallas_kernels.paged_decode_attention`` at call time), so no other
    op's per-process jit sees interpret mode; the op's own jits are
    forgotten before and after. The value is a list that gets one entry a
    trace: whether that call stored rows."""
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.ops.registry import get_op

    stored = []
    real = pk.paged_decode_attention

    def through_the_kernel(*args):
        stored.append(len(args) > 7)
        with monkeypatch.context() as mp:
            mp.setattr(pk, "_use_pallas", lambda: True)
            mp.setattr(pk, "_interpret", lambda: True)
            return real(*args)

    op = get_op("paged_decode_attention")
    op._fn_cache.clear()
    monkeypatch.setattr(pk, "paged_decode_attention", through_the_kernel)
    yield stored
    op._fn_cache.clear()


def pytest_configure(config):
    config.addinivalue_line("markers", "seed(n): fix the RNG seed for a test")
    config.addinivalue_line("markers", "serial: run in isolation")
    config.addinivalue_line("markers", "integration: end-to-end tests")
    config.addinivalue_line(
        "markers", "chaos: fault-injection tests (MXTPU_FAULT_* harness)")
    config.addinivalue_line(
        "markers",
        "slow: nightly-scale sweeps excluded from the default (tier-1) run")
