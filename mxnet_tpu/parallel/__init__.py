"""Parallelism over device meshes — the TPU-native replacement for the
reference's KVStore/ps-lite/NCCL stack (ref: SURVEY §2.4/§5).

Design: pick a Mesh, annotate shardings, let XLA insert collectives over
ICI/DCN (psum/all_gather/reduce_scatter compiled into the step) — instead of
translating worker/server push/pull. The KVStore API survives as a facade
(mxnet_tpu/kvstore.py); this package holds the real machinery:

- mesh.py: mesh construction + distributed init (multi-host)
- sharded.py: sharded training-step builder over Gluon blocks
  (data/tensor parallel via PartitionSpec rules; ZeRO-1/2/3
  weight-update sharding over the data axis)
- reshard.py: elastic in-place mesh resharding when membership fences
  a dead host (CheckpointManager shards as the transfer format)
- sequence.py: sequence parallelism (ring and Ulysses attention) over
  an sp mesh axis
- unified.py: 4D composition — pipeline stages + MoE experts as
  rule-sharded stacked params on a dp×tp×pp×ep mesh, trained by the
  SAME one-launch ShardedTrainStep (no eager island dispatch)
"""
from .mesh import (
    make_mesh, data_parallel_mesh, init_distributed, local_device_count,
)
from .sharded import (
    ShardedTrainStep, shard_params, sharding_rule, allreduce_across_processes,
)
from .reshard import (
    ElasticReshardController, HostDeviceMap, plan_survivor_mesh,
    reshard_step,
)
from .sequence import (current_sequence_scope, ring_attention,
                       sequence_scope, ulysses_attention)
from .unified import (
    PipelineMoEBlock, pipeline_moe_forward, publish_moe_telemetry,
    moe_capacity, resolve_mesh_axis,
)

__all__ = ["make_mesh", "data_parallel_mesh", "init_distributed",
           "local_device_count", "ShardedTrainStep", "shard_params",
           "sharding_rule", "allreduce_across_processes",
           "ElasticReshardController", "HostDeviceMap",
           "plan_survivor_mesh", "reshard_step", "ring_attention",
           "ulysses_attention", "sequence_scope",
           "current_sequence_scope", "PipelineMoEBlock",
           "pipeline_moe_forward", "publish_moe_telemetry",
           "moe_capacity", "resolve_mesh_axis"]
