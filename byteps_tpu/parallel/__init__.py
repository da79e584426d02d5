"""byteps_tpu.parallel — mesh construction, collectives, sharding rules,
and the model-parallel axes (tp/pp/sp/ep) that generalize the reference's
data-parallel-only design (SURVEY.md §2.4)."""

from .mesh import AXIS_ORDER, axis_size, build_mesh, parse_mesh_shape, reduce_axes, world_size
from .collectives import (
    broadcast_shard,
    broadcast_stacked,
    push_pull_shard,
    push_pull_stacked,
    push_pull_tree,
    replicate,
    shard_map,
)
from .ring_attention import local_attention, ring_attention, ulysses_attention
from .pipeline import pipeline_apply, pipeline_loss
from .moe import expert_layer, route

__all__ = [
    "AXIS_ORDER", "build_mesh", "parse_mesh_shape", "reduce_axes",
    "axis_size", "world_size",
    "push_pull_shard", "push_pull_tree", "push_pull_stacked",
    "broadcast_shard", "broadcast_stacked", "replicate", "shard_map",
    "ring_attention", "ulysses_attention", "local_attention",
    "pipeline_apply", "pipeline_loss",
    "expert_layer", "route",
]
