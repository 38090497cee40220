"""Round-4 distributed tail: object collectives, gloo host group,
ParallelEnv, Placement, split, shard_optimizer, unshard_dtensor.

Reference: python/paddle/distributed/{parallel,collective}.py and
auto_parallel/api.py (SURVEY §2.4 Python comm API row).
"""

from __future__ import annotations

import os
import pickle

import numpy as np
import jax
import jax.numpy as jnp

from .auto import Partial, Replicate, Shard
from .communication import (ReduceOp, all_gather, all_reduce, broadcast,
                            get_rank, get_world_size, scatter)


# ---------------------------------------------------------------------------
# object collectives (pickle over the byte-tensor collectives, exactly the
# reference's _convert_object_to_tensor scheme)
# ---------------------------------------------------------------------------

def _padded_size(nbytes: int, group=None) -> int:
    """Collective byte-buffer size for an ``nbytes`` pickle: the next
    256-byte multiple, MAX-REDUCED across the group's ranks (ADVICE r5).

    The reference sizes the tensor to the object (ADVICE r4); small
    objects no longer move a fixed 1 MB and large ones are no longer
    rejected.  In the single-controller SPMD model the local pickle is
    identical on every rank by construction, so the max-reduce is a
    cheap identity — but a per-rank-divergent payload (a bug today, a
    multi-process object path tomorrow) now pads every rank to the
    global maximum, so the byte collective runs with agreeing shapes
    and the truth surfaces in the unpickled objects, instead of an XLA
    shape mismatch (or silent corruption) downstream.  Explicit
    ``max_bytes`` callers (scatter) keep the loud over-budget raise in
    ``_obj_to_padded``."""
    padded = max(256, (nbytes + 255) // 256 * 256)
    try:
        agreed = int(all_reduce(jnp.asarray(padded, jnp.int32),
                                op=ReduceOp.MAX, group=group))
    except Exception:
        # no mesh / no parallel env: single-rank, local size is global
        return padded
    return max(padded, agreed)


def _obj_to_padded(obj, max_bytes=None, group=None):
    raw = pickle.dumps(obj)
    size = max_bytes if max_bytes is not None \
        else _padded_size(len(raw), group=group)
    if len(raw) > size:
        raise ValueError(f"object of {len(raw)} bytes exceeds the "
                         f"{size}-byte object-collective budget")
    buf = np.zeros((size + 8,), np.uint8)
    buf[:8] = np.frombuffer(np.int64(len(raw)).tobytes(), np.uint8)
    buf[8:8 + len(raw)] = np.frombuffer(raw, np.uint8)
    return jnp.asarray(buf)


def _padded_to_obj(buf):
    b = np.asarray(buf).astype(np.uint8)
    n = int(np.frombuffer(b[:8].tobytes(), np.int64)[0])
    return pickle.loads(b[8:8 + n].tobytes())


def all_gather_object(object_list, obj, group=None):
    """Reference: paddle.distributed.all_gather_object — every rank
    contributes one picklable object; all ranks receive all of them."""
    gathered = []
    all_gather(gathered, _obj_to_padded(obj, group=group), group=group)
    object_list.extend(_padded_to_obj(t) for t in gathered)
    return object_list


def broadcast_object_list(object_list, src=0, group=None):
    """Reference: paddle.distributed.broadcast_object_list (in place)."""
    if not object_list:
        return object_list
    # one group max-reduce over the local max, not one per element (the
    # scatter path's convention); elements then share one buffer size
    common = _padded_size(max(len(pickle.dumps(o)) for o in object_list),
                          group=group)
    for i, obj in enumerate(object_list):
        t = broadcast(_obj_to_padded(obj, max_bytes=common), src=src,
                      group=group)
        object_list[i] = _padded_to_obj(t)
    return object_list


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """Reference: paddle.distributed.scatter_object_list.

    SPMD note: every rank runs the same program over global values, so —
    unlike the reference's per-rank processes — ``in_object_list`` must
    be passed on ALL ranks (it is the same global list everywhere); the
    reference's pass-None-on-non-src convention has no meaning here."""
    if in_object_list is None:
        raise ValueError(
            "scatter_object_list: in_object_list must be provided on every "
            "rank — SPMD programs see the same global inputs (the "
            "reference's None-on-non-src convention does not apply)")
    # one shared buffer size: scatter stacks the buffers, so DIFFERENT
    # objects (the whole point of scatter) must pad to the max pickle;
    # one group max-reduce over the local max, not one per element
    common = _padded_size(max(len(pickle.dumps(o)) for o in in_object_list),
                          group=group)
    tensors = [_obj_to_padded(o, max_bytes=common) for o in in_object_list]
    got = scatter(None, tensor_list=tensors, src=src, group=group)
    if got is None:  # world of 1 (no comm context): src keeps its element
        out_object_list.append(in_object_list[src])
        return out_object_list
    got = np.asarray(got)
    if got.ndim == 2:  # eager global form keeps the group dim (see scatter)
        got = got[get_rank(group)]
    out_object_list.append(_padded_to_obj(got))
    return out_object_list


# ---------------------------------------------------------------------------
# process-group lifecycle / introspection
# ---------------------------------------------------------------------------

def is_available() -> bool:
    """Reference: paddle.distributed.is_available."""
    return True


def get_backend(group=None) -> str:
    """Reference: paddle.distributed.get_backend — the comm transport.
    XLA emits collectives over ICI/DCN on TPU and shared-memory on the
    CPU mesh; 'XLA' names both (NCCL/GLOO dissolve per SURVEY §7.3)."""
    return "XLA"


def get_group(id=0):
    """Reference: paddle.distributed.get_group — group registry lookup."""
    from .communication import Group
    reg = getattr(get_group, "_registry", None)
    if reg and id in reg:
        return reg[id]
    return Group(("dp",))


def destroy_process_group(group=None):
    """Reference: paddle.distributed.destroy_process_group — tear down the
    bootstrap (jax.distributed) connection; mesh-axis groups are pure
    values and need no teardown.  Destroying a SUBGROUP (``group`` given,
    valid reference usage) is therefore a no-op here — it must NOT tear
    down the global bootstrap for everyone (ADVICE r4)."""
    if group is not None:
        return
    try:
        jax.distributed.shutdown()
    except Exception:
        pass  # not initialized — matches the reference's idempotent call


def wait(tensor, group=None, use_calc_stream=True):
    """Reference: paddle.distributed.wait — block until the tensor's
    producing computation (including collectives) lands."""
    return jax.block_until_ready(tensor)


# ---------------------------------------------------------------------------
# gloo host group — CPU-side barrier/bootstrap over the native TCPStore
# (reference: paddle.distributed.gloo_init_parallel_env / gloo_barrier /
# gloo_release over an actual gloo context)
# ---------------------------------------------------------------------------

_gloo = {"store": None, "rank": 0, "world": 1, "gen": 0}


def gloo_init_parallel_env(rank_id, rank_num, server_endpoint=None):
    from ..launch.store import TCPStore
    ep = server_endpoint or os.environ.get("PADDLE_GLOO_HTTP_ENDPOINT",
                                           "127.0.0.1:6170")
    _gloo["store"] = TCPStore(ep, is_master=(int(rank_id) == 0))
    _gloo["rank"], _gloo["world"] = int(rank_id), int(rank_num)
    _gloo["gen"] = 0


def gloo_barrier():
    st = _gloo["store"]
    if st is None:
        raise RuntimeError("gloo_barrier: call gloo_init_parallel_env first")
    _gloo["gen"] += 1
    key = f"gloo/barrier/{_gloo['gen']}"
    st.add(key, 1)
    import time
    deadline = time.time() + 300.0
    while time.time() < deadline:
        v = st.get(key)
        if v is not None and int(v) >= _gloo["world"]:
            return
        time.sleep(0.01)
    raise TimeoutError("gloo_barrier timed out")


def gloo_release():
    st = _gloo.pop("store", None)
    _gloo.update(store=None, rank=0, world=1, gen=0)
    if st is not None and hasattr(st, "close"):
        st.close()


# ---------------------------------------------------------------------------
# legacy env / placement / strategy surface
# ---------------------------------------------------------------------------

class ParallelEnv:
    """Reference: paddle.distributed.ParallelEnv — env-derived rank info
    (the pre-fleet legacy API; still widely used in ported scripts)."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def device_id(self):
        sel = os.environ.get("FLAGS_selected_gpus") or \
            os.environ.get("TPU_VISIBLE_CHIPS") or "0"
        return int(sel.split(",")[0])

    @property
    def nranks(self):
        return self.world_size

    @property
    def local_rank(self):
        return int(os.environ.get("PADDLE_LOCAL_RANK", self.rank))


class _PlacementMeta(type):
    def __instancecheck__(cls, obj):
        return isinstance(obj, (Shard, Replicate, Partial))


class Placement(metaclass=_PlacementMeta):
    """Reference: paddle.distributed.Placement — the common base of
    Shard/Replicate/Partial.  isinstance() works against all three."""


def Strategy(config=None):
    """Reference: paddle.distributed.Strategy (auto-parallel config) —
    the same knobs live on fleet.DistributedStrategy here."""
    from .fleet import DistributedStrategy
    s = DistributedStrategy()
    for k, v in (config or {}).items():
        setattr(s, k, v)
    return s


# ---------------------------------------------------------------------------
# split / shard_optimizer / unshard_dtensor
# ---------------------------------------------------------------------------

def split(x, size, operation, axis=0, num_partitions=None, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Reference: paddle.distributed.split — build a model-parallel
    linear/embedding sharded along ``axis`` over the mp mesh axis.
    Delegates to the mp_layers implementations (SURVEY §2.5 TP row)."""
    from .mp_layers import (ColumnParallelLinear, RowParallelLinear,
                            VocabParallelEmbedding)
    if operation == "linear":
        in_f, out_f = size
        if axis == 1:
            layer = ColumnParallelLinear(in_f, out_f,
                                         gather_output=gather_out)
        else:
            layer = RowParallelLinear(in_f, out_f)
        return layer(x)
    if operation == "embedding":
        vocab, dim = size
        layer = VocabParallelEmbedding(vocab, dim)
        return layer(x)
    raise ValueError("operation must be 'linear' or 'embedding'")


def shard_optimizer(optimizer, shard_fn=None):
    """Reference: paddle.distributed.shard_optimizer — ZeRO-style
    partitioning of optimizer states over the data-parallel axis; the
    stage-1 sharded wrapper implements exactly that."""
    from .sharding import DygraphShardingOptimizer
    del shard_fn  # partition policy is the dp-axis ZeRO-1 layout
    return DygraphShardingOptimizer(optimizer)


def unshard_dtensor(dist_tensor):
    """Reference: paddle.distributed.unshard_dtensor — gather a sharded
    array into a fully-replicated one."""
    return jnp.asarray(np.asarray(dist_tensor))
