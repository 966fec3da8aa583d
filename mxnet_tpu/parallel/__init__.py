"""mxnet_tpu.parallel — SPMD scale-out over TPU meshes.

This package is the TPU-native answer to everything the reference does with
NCCL + ps-lite (SURVEY §2.2, §5.8): instead of push/pull of gradients between
processes, the WHOLE training step is one pjit-compiled SPMD program over a
``jax.sharding.Mesh`` whose collectives ride ICI/DCN. Axes follow the
scaling-book convention: ``dp`` (data), ``tp`` (tensor/model), ``pp``
(pipeline), ``sp`` (sequence/context), ``ep`` (expert).

- mesh.py        — mesh construction + sharding helpers
- collectives.py — psum/all_gather/ppermute wrappers for shard_map kernels
- partition.py   — regex partition rules over named param trees (FSDP/tp)
- learner.py     — Learner: gluon Block -> jitted sharded train step
"""
from .mesh import (make_mesh, default_mesh, replicated, shard_batch,
                   shard_params, AxisNames)
from .collectives import (all_reduce, all_gather, reduce_scatter, ppermute,
                          axis_index, axis_size)
from .partition import (match_partition_rules, named_tree_map, fsdp_rules,
                        spec_axes)
from .learner import Learner, to_optax
from .ring_attention import ring_attention, ring_attention_sharded
from .pipeline import pipeline_apply, pipeline_sharded
from .moe import moe_apply, moe_sharded, RoutedExperts, TopKRouter
from .five_axis import (build_five_axis_train_step, init_five_axis_params,
                        five_axis_specs)

__all__ = ["make_mesh", "default_mesh", "replicated", "shard_batch",
           "shard_params", "AxisNames", "all_reduce", "all_gather",
           "reduce_scatter", "ppermute", "axis_index", "axis_size",
           "Learner", "to_optax", "ring_attention",
           "ring_attention_sharded", "pipeline_apply", "pipeline_sharded",
           "moe_apply", "moe_sharded", "RoutedExperts", "TopKRouter",
           "build_five_axis_train_step",
           "init_five_axis_params", "five_axis_specs",
           "match_partition_rules", "named_tree_map", "fsdp_rules",
           "spec_axes"]
