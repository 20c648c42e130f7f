"""Parallelism: device meshes, shardings, and the sharded train step."""

from raft_tpu.parallel.mesh import (
    BATCH_SPEC,
    WINDOW_BATCH_SPEC,
    batch_sharding,
    initialize_distributed,
    make_mesh,
    replicated,
    shard_batch,
    traced_under,
    window_batch_sharding,
)
from raft_tpu.parallel.serve_shard import (
    make_serve_mesh,
    row_sharding,
    scale_rungs,
)
from raft_tpu.parallel.sharded_step import (
    make_sharded_train_step,
    make_sharded_window_step,
    shard_state,
)

__all__ = [
    "BATCH_SPEC",
    "WINDOW_BATCH_SPEC",
    "batch_sharding",
    "initialize_distributed",
    "make_mesh",
    "replicated",
    "shard_batch",
    "traced_under",
    "window_batch_sharding",
    "make_serve_mesh",
    "row_sharding",
    "scale_rungs",
    "make_sharded_train_step",
    "make_sharded_window_step",
    "shard_state",
]
