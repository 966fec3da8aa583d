"""Operator library: importing this package registers all built-in ops."""
from .registry import Op, register, get_op, list_ops, invoke, apply_op
from . import _core  # noqa: F401 — registers elemwise/reduce/shape/linalg ops
from . import nn  # noqa: F401 — registers NN ops
from . import indexing  # noqa: F401 — registers slice/scatter ops
from . import rnn  # noqa: F401 — registers the fused scan RNN op
from . import vision  # noqa: F401 — registers detection/resize/ROI ops
from . import extra  # noqa: F401 — legacy tensor/transformer/multibox ops
from . import linalg_legacy  # noqa: F401 — mx.nd.linalg_* family
from . import optimizer_ops  # noqa: F401 — fused update ops incl. sparse
from . import legacy_elemwise  # noqa: F401 — scalar/creation/slice legacy tiers
from . import random_ops  # noqa: F401 — _random_/_sample_/_npi_ sampler ops
from . import quantized_ops  # noqa: F401 — int8 quantized family + intgemm
from . import graph_image_ops  # noqa: F401 — sldwin attention, dgl, image/cv
from . import npi_manip  # noqa: F401 — dynamic-shape manip, control flow, contrib
from . import warp_ops  # noqa: F401 — STN/deformable/correlation tier
from . import tp_collectives  # noqa: F401 — megatron tp collectives
from . import delta_rule  # noqa: F401 — chunked gated delta rule, causal conv
from . import moe  # noqa: F401 — router and dropless routed experts
from . import aliases as _aliases  # reference-name aliases (NNVM add_alias analog)

_aliases._register_all()

__all__ = ["Op", "register", "get_op", "list_ops", "invoke", "apply_op"]
