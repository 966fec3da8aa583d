"""mxnet_tpu — a TPU-native deep learning framework with MXNet's capabilities.

A from-scratch redesign (NOT a port) of apache/incubator-mxnet for TPU:
jax/XLA/Pallas for compute, PJRT async dispatch instead of a threaded engine,
whole-graph jit (CachedOp) instead of nnvm graph replay, XLA collectives over
ICI/DCN instead of NCCL/ps-lite. See SURVEY.md in the repo root for the
component-by-component mapping to the reference.

Typical use mirrors MXNet 2.0::

    import mxnet_tpu as mx
    from mxnet_tpu import np, npx, gluon, autograd

    net = gluon.nn.Dense(10)
    net.initialize(ctx=mx.tpu())
    with autograd.record():
        loss = net(np.ones((2, 5))).sum()
    loss.backward()
"""
from __future__ import annotations

__version__ = "0.1.0"

from .base import MXNetError
from .context import (Context, Device, cpu, cpu_pinned, gpu, tpu, device,
                      current_context, current_device, num_gpus, num_tpus,
                      tpu_memory_info, gpu_memory_info)
from . import engine
from . import dlpack
from . import error
from . import libinfo
from . import log
from . import ops
from .ndarray.ndarray import NDArray, array, from_jax
from . import autograd
from . import random
from . import numpy as np
from . import numpy_extension as npx
from .symbol import Symbol, var
from . import symbol as sym
from .cached_op import CachedOp
from . import _deferred_compute

# subsystems
from . import initializer
from . import optimizer
from . import lr_scheduler
from . import kvstore
from .kvstore import KVStore
from . import gluon
from . import nd
from . import metric
from . import io
from . import image
from . import recordio
from . import operator
from . import library
from . import subgraph
from . import contrib
from . import rtc
from . import utils
from . import name
from . import attribute
from .attribute import AttrScope
from .name import NameManager
from . import visualization
from . import callback
from . import model
from .ndarray import sparse
from . import profiler
from . import telemetry
from . import monitor
from . import runtime
from . import util
from . import parallel
from . import amp
from . import serve
from . import checkpoint
from . import testing

kv = kvstore

# late-registered ops (e.g. contrib.quantization's quantize/dequantize) get
# their reference-name aliases now that every subpackage has imported
ops.aliases._register_all()


def waitall():
    engine.wait_all()


test_utils = None  # populated lazily to keep import light


def __getattr__(name):
    if name == "test_utils":
        from . import test_utils as tu

        globals()["test_utils"] = tu
        return tu
    raise AttributeError(f"module 'mxnet_tpu' has no attribute {name!r}")
