"""``paddle_tpu.distributed`` — hybrid parallelism over TPU meshes.

Subsystem map (reference SURVEY.md §2.4/2.5):
- fleet: topology/strategy orchestration (fleet.init + hybrid_configs)
- communication: collective API (all_reduce/.../p2p_shift) over mesh axes
- mp_layers: tensor-parallel layers + Megatron-SP
- pipeline: 1F1B/GPipe pipeline parallel via shard_map + ppermute
- sharding: ZeRO stage 1/2/3 semantics (group_sharded_parallel)
- moe: expert parallel MoE layer (all_to_all dispatch), and DroplessMoE:
  routing as data over the experts held here, no capacity, no drop
- cp: context parallelism (Ulysses all_to_all + ring attention)
- auto: shard_tensor / reshard (auto-parallel DistTensor parity)
"""

from . import fleet  # noqa: F401
from .topology import AXIS_ORDER, HybridCommunicateGroup, HybridTopology  # noqa: F401
from .communication import (ReduceOp, Group, new_group, all_reduce,  # noqa: F401
                            all_gather, reduce_scatter, alltoall,
                            alltoall_single, broadcast, reduce, scatter,
                            send, recv, isend, irecv, P2POp, P2PTask,
                            batch_isend_irecv, p2p_shift, barrier, get_rank,
                            get_world_size, is_initialized,
                            init_parallel_env)
from .mp_layers import (ColumnParallelLinear, RowParallelLinear,  # noqa: F401
                        VocabParallelEmbedding, ParallelCrossEntropy,
                        ColumnSequenceParallelLinear,
                        RowSequenceParallelLinear,
                        scatter_to_sequence_parallel,
                        gather_from_sequence_parallel,
                        mark_as_sequence_parallel_parameter)
from .auto import (DistAttr, Partial, PartialTensor,  # noqa: F401
                   ProcessMesh, Replicate, Shard, ShardDataloader,
                   dtensor_from_fn, reshard, shard_dataloader, shard_layer,
                   shard_tensor)
from .parallel import DataParallel  # noqa: F401
from .engine import DistModel, Engine, to_static  # noqa: F401
from .recompute import recompute, RecomputeWrapper  # noqa: F401
from .pipeline import (LayerDesc, SharedLayerDesc, PipelineLayer,  # noqa: F401
                       PipelineParallel, StackedPipelineStages)
from . import sharding  # noqa: F401
from .sharding import group_sharded_parallel  # noqa: F401
from . import moe  # noqa: F401
from .moe import DroplessMoE, MoELayer  # noqa: F401
from . import cp  # noqa: F401
from .cp import (ring_attention, ulysses_attention,  # noqa: F401
                 context_parallel_attention)
from .spawn import spawn  # noqa: F401
from . import rpc  # noqa: F401
from . import ps  # noqa: F401
from . import stream  # noqa: F401

# paddle.distributed.save_state_dict / load_state_dict parity (reference:
# python/paddle/distributed/checkpoint/) — implemented in paddle_tpu.ckpt
# with cross-topology reshard-on-load
from ..ckpt import load_state_dict, save_state_dict  # noqa: F401

# round-4 tail: object collectives, gloo host group, ParallelEnv,
# Placement, split/shard_optimizer/unshard_dtensor — see misc.py
from .misc import (  # noqa: F401
    ParallelEnv, Placement, Strategy, all_gather_object,
    broadcast_object_list, destroy_process_group, get_backend, get_group,
    gloo_barrier, gloo_init_parallel_env, gloo_release, is_available,
    scatter_object_list, shard_optimizer, split, unshard_dtensor, wait)


def __getattr__(name):
    if name == "checkpoint":  # paddle.distributed.checkpoint module alias
        from .. import ckpt
        return ckpt
    if name == "launch":  # paddle.distributed.launch module alias
        from .. import launch
        return launch
    raise AttributeError(f"module 'paddle_tpu.distributed' has no attribute {name!r}")


def get_hybrid_communicate_group():
    return fleet.get_hybrid_communicate_group()
