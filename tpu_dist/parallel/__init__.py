"""tpu_dist.parallel — parallelism wrappers (L3 of SURVEY.md §1).

Data parallelism is the reference's only strategy (SURVEY.md §2c); the mesh
design leaves room for tp/pp/sp axes (ProcessGroup accepts custom
axis_names/mesh_shape)."""

from .ddp import (DistributedDataParallel, TrainState,
                  convert_sync_batchnorm)
from .fsdp import fsdp_shard, fsdp_specs
from .gspmd import (MOE_EP_RULES, PartitionRules, TRANSFORMER_TP_RULES,
                    make_gspmd_train_step, shard_pytree)
from .mesh import get_mesh, mesh_shape_for
from .pipeline import PipelineParallel, PipeTrainState
from .ring_attention import ring_self_attention, ulysses_self_attention
from .rules import (DEFAULT_RULES, SERVING_RULES, LeafLayout,
                    ShardLayoutError, TRANSFORMER_LAYOUTS, chunk_bounds,
                    chunk_span, layout_for, mapped_axes, model_axes,
                    partition_pairs, shard_leaf, spans_for, spec_for,
                    spec_for_key)
from .zero import ZeroOptimizer, ZeroParams, ZeroStateError

# torch-style alias (the reference imports nn.parallel.DistributedDataParallel)
DDP = DistributedDataParallel

__all__ = ["DistributedDataParallel", "DDP", "TrainState",
           "convert_sync_batchnorm",
           "PartitionRules", "TRANSFORMER_TP_RULES", "MOE_EP_RULES",
           "make_gspmd_train_step", "shard_pytree",
           "PipelineParallel", "PipeTrainState",
           "fsdp_shard", "fsdp_specs",
           "get_mesh", "mesh_shape_for",
           "DEFAULT_RULES", "SERVING_RULES", "LeafLayout",
           "ShardLayoutError", "TRANSFORMER_LAYOUTS", "chunk_bounds",
           "chunk_span", "layout_for", "mapped_axes", "model_axes",
           "partition_pairs", "shard_leaf", "spans_for", "spec_for",
           "spec_for_key",
           "ring_self_attention", "ulysses_self_attention",
           "ZeroOptimizer", "ZeroParams", "ZeroStateError"]
