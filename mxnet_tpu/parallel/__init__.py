"""Parallelism over the TPU device mesh.

TPU-native replacement for the reference's kvstore/ps-lite/NCCL stack
(SURVEY §2.5): a `jax.sharding.Mesh` with named axes (dp/tp/pp/sp) plus
pjit/shard_map; XLA emits the collectives over ICI/DCN.

- mesh:        mesh construction helpers + global default mesh
- collectives: axis-name bookkeeping + psum/all_gather wrappers
- step:        compiled data/tensor-parallel training step builder, out of
               layout (where its arrays live), exchange (a gradient's
               way and its wire bytes) and update (optimizer kernels)
- dist:        multi-process init (jax.distributed), launch.py analog,
               elastic membership side channel (heartbeats, peer-loss
               detection, re-form barrier — MXTPU_ELASTIC)
- ring_attention: sequence-parallel ring attention over ppermute
"""
from .mesh import (make_mesh, default_mesh, set_default_mesh, mesh_shape,
                   data_parallel_spec, replicate_spec)
from . import collectives
from .layout import compose_zero_spec, zero3_layout
from .step import ShardedTrainStep
from . import dist
from .ring_attention import ring_attention
from .pipeline import (pipeline_forward, pipeline_loss_fn,
                       pipeline_composite_loss, PipelineTrainStep,
                       stack_stage_params, split_layers_into_stages)
