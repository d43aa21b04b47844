"""Collective communication API.

Reference: python/paddle/distributed/communication/ (+ ``Group`` at
communication/group.py:29, ``new_group`` at collective.py:195) over
ProcessGroupNCCL (process_group_nccl.cc:267).

TPU-native design (SURVEY.md §5): collectives are *in-program* XLA ops over ICI.
Two execution modes, same API:

- **traced** (inside pjit/shard_map with the group's mesh axis in scope): lowers
  to ``lax.psum/all_gather/ppermute/psum_scatter`` — the performance path; XLA
  schedules them on ICI and overlaps with compute (the role of NCCL streams +
  the comm-overlap machinery in the reference).
- **eager** (single controller): per-rank values are held as one global array
  stacked along a leading "rank" dim (sharded over devices when a mesh is
  active).  The collective is ordinary jnp math on that global view — on sharded
  inputs XLA still emits the real ICI transfers.

Rank-local views are materialized with ``to_rank_list`` / built with
``from_rank_list`` — the single-controller analog of each process holding its
local tensor.
"""

from __future__ import annotations

import os
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor, _unwrap, apply_op, no_grad
from .env import get_world_size

__all__ = [
    "ReduceOp",
    "Group",
    "new_group",
    "get_group",
    "all_reduce",
    "all_gather",
    "all_gather_object",
    "reduce",
    "reduce_scatter",
    "alltoall",
    "alltoall_single",
    "broadcast",
    "scatter",
    "gather",
    "send",
    "recv",
    "isend",
    "irecv",
    "barrier",
    "from_rank_list",
    "to_rank_list",
    "P2POp",
    "batch_isend_irecv",
    "wait",
    "stream",
    "destroy_process_group",
    "broadcast_object_list",
    "scatter_object_list",
    "split",
]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


_groups: dict[int, "Group"] = {}
_lock = threading.Lock()
_next_gid = [0]


class Group:
    """A communicator = an ordered set of device ranks + a mesh axis name."""

    def __init__(self, ranks: Sequence[int] | None = None, axis_name: str | None = None, gid: int | None = None):
        ndev = jax.device_count()
        self.ranks = list(range(ndev)) if ranks is None else list(ranks)
        self.axis_name = axis_name or f"pg{gid if gid is not None else 0}"
        self.id = gid if gid is not None else 0
        devices = jax.devices()
        self.devices = [devices[r] for r in self.ranks if r < len(devices)]

    @property
    def nranks(self) -> int:
        return len(self.ranks)

    @property
    def world_size(self) -> int:
        return self.nranks

    @property
    def rank(self) -> int:
        """This process's position in the group.

        Under multi-process (launch CLI / jax.distributed) this is the
        process rank's index in ``ranks`` (-1 if not a member), mirroring
        ProcessGroup::GetRank.  Single-controller keeps the rank-0
        convention (the controller drives every rank)."""
        pid = _process_rank()
        if pid == 0 and _process_count() == 1:
            return 0
        return self.ranks.index(pid) if pid in self.ranks else -1

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(id={self.id}, nranks={self.nranks}, axis={self.axis_name!r})"

    process_group = property(lambda self: self)


def new_group(ranks=None, backend=None, timeout=None) -> Group:
    with _lock:
        _next_gid[0] += 1
        gid = _next_gid[0]
        g = Group(ranks, gid=gid)
        _groups[gid] = g
        return g


def get_group(id: int = 0) -> Group:
    if id == 0 and 0 not in _groups:
        _groups[0] = Group(gid=0)
    return _groups[id]


def _default_group() -> Group:
    return get_group(0)


def _is_traced(v) -> bool:
    return isinstance(v, jax.core.Tracer)


def _axis_in_scope(name: str) -> bool:
    try:
        jax.lax.axis_index(name)  # raises NameError if axis not bound
        return True
    except Exception:
        return False


# ---- rank-view helpers (single-controller bridge) ----

def from_rank_list(tensors, group=None) -> Tensor:
    """Stack per-rank local tensors into the global stacked view [nranks, ...]."""
    vals = [_unwrap(t) for t in tensors]
    return Tensor(jnp.stack(vals, axis=0))


def to_rank_list(x, group=None) -> list[Tensor]:
    v = _unwrap(x)
    return [Tensor(v[i]) for i in range(v.shape[0])]


def _reduce_stacked(v, op):
    if op in (ReduceOp.SUM, "sum"):
        return jnp.sum(v, axis=0, keepdims=True)
    if op in (ReduceOp.MAX, "max"):
        return jnp.max(v, axis=0, keepdims=True)
    if op in (ReduceOp.MIN, "min"):
        return jnp.min(v, axis=0, keepdims=True)
    if op in (ReduceOp.PROD, "prod"):
        return jnp.prod(v, axis=0, keepdims=True)
    if op in (ReduceOp.AVG, "avg"):
        return jnp.mean(v, axis=0, keepdims=True)
    raise ValueError(f"unsupported reduce op {op}")


def _lax_reduce(v, op, axis_name):
    if op in (ReduceOp.SUM, "sum"):
        return jax.lax.psum(v, axis_name)
    if op in (ReduceOp.MAX, "max"):
        return jax.lax.pmax(v, axis_name)
    if op in (ReduceOp.MIN, "min"):
        return jax.lax.pmin(v, axis_name)
    if op in (ReduceOp.AVG, "avg"):
        return jax.lax.pmean(v, axis_name)
    if op in (ReduceOp.PROD, "prod"):
        return jnp.exp(jax.lax.psum(jnp.log(v), axis_name))
    raise ValueError(f"unsupported reduce op {op}")


# ---- collectives ----

def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True, use_calc_stream=False):
    """NOTE eager mode: non-differentiable (reference parity) — executed under
    no_grad so the tape records nothing; in-program (traced) use lowers to
    lax collectives which ARE differentiable under jax.grad."""
    group = group or _default_group()
    v = _unwrap(tensor)
    if _is_traced(v) and _axis_in_scope(group.axis_name):
        out = _lax_reduce(v, op, group.axis_name)
        return Tensor(out) if isinstance(tensor, Tensor) else out
    # eager stacked view: every rank slot gets the reduction
    def fn(val):
        red = _reduce_stacked(val, op)
        return jnp.broadcast_to(red, val.shape)

    with no_grad():
        out = apply_op("all_reduce", fn, [tensor])
    if isinstance(tensor, Tensor):
        tensor._value = out._value  # paddle all_reduce is in-place
        return tensor
    return out


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True, use_calc_stream=False):
    group = group or _default_group()
    v = _unwrap(tensor)
    if _is_traced(v) and _axis_in_scope(group.axis_name):
        return Tensor(_lax_reduce(v, op, group.axis_name))

    def fn(val):
        red = _reduce_stacked(val, op)[0]
        return val.at[group.ranks.index(dst) if dst in group.ranks else dst].set(red)

    with no_grad():
        out = apply_op("reduce", fn, [tensor])
    if isinstance(tensor, Tensor):
        tensor._value = out._value
        return tensor
    return out


def all_gather(tensor_list, tensor=None, group=None, sync_op=True, use_calc_stream=False, axis=0):
    group = group or _default_group()
    if isinstance(tensor_list, list) and tensor is not None:
        # paddle API: all_gather(tensor_list, tensor) — stacked eager mode
        v = _unwrap(tensor)
        if v.ndim == 0:
            raise ValueError("all_gather requires >=1-D tensor")
        # stacked global [nranks, ...local]: gathered result is every slot
        parts = [Tensor(v[i]) for i in range(v.shape[0])]
        tensor_list.extend(parts)
        return tensor_list
    x = tensor_list
    v = _unwrap(x)
    if _is_traced(v) and _axis_in_scope(group.axis_name):
        out = jax.lax.all_gather(v, group.axis_name, axis=axis, tiled=True)
        return Tensor(out) if isinstance(x, Tensor) else out

    def fn(val):
        # [nranks, ...loc] -> every slot holds concat of locals along `axis`
        parts = [val[i] for i in range(val.shape[0])]
        cat = jnp.concatenate(parts, axis=axis)
        return jnp.broadcast_to(cat[None], (val.shape[0],) + cat.shape)

    with no_grad():
        return apply_op("all_gather", fn, [x])


def all_gather_object(object_list, obj, group=None):
    object_list.append(obj)
    return object_list


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None, sync_op=True, use_calc_stream=False, axis=0):
    group = group or _default_group()
    v = _unwrap(tensor)
    if _is_traced(v) and _axis_in_scope(group.axis_name):
        out = jax.lax.psum_scatter(v, group.axis_name, scatter_dimension=axis, tiled=True)
        return Tensor(out) if isinstance(tensor, Tensor) else out
    n = group.nranks

    def fn(val):
        red = _reduce_stacked(val, op)[0]  # [...global]
        chunks = jnp.stack(jnp.split(red, val.shape[0], axis=axis), axis=0)
        return chunks  # slot i = its reduced chunk

    with no_grad():
        return apply_op("reduce_scatter", fn, [tensor])


def alltoall(out_tensor_list, in_tensor_list=None, group=None, sync_op=True, use_calc_stream=False):
    group = group or _default_group()
    # stacked eager form: single tensor [nranks, nranks, ...] OR paddle list API
    if isinstance(out_tensor_list, Tensor) and in_tensor_list is None:
        x = out_tensor_list
        v = _unwrap(x)
        if _is_traced(v) and _axis_in_scope(group.axis_name):
            out = jax.lax.all_to_all(v, group.axis_name, split_axis=0, concat_axis=0, tiled=True)
            return Tensor(out)
        with no_grad():
            return apply_op("alltoall", lambda val: jnp.swapaxes(val, 0, 1), [x])
    # list API: in_tensor_list[i] is this "rank"'s message to rank i — with the
    # stacked convention inputs are [nranks][nranks, ...]
    ins = [_unwrap(t) for t in in_tensor_list]
    stacked = jnp.stack(ins, axis=0)  # [dst, src, ...]
    out = jnp.swapaxes(stacked, 0, 1)
    res = [Tensor(out[i]) for i in range(out.shape[0])]
    out_tensor_list.extend(res)
    return out_tensor_list


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None, out_split_sizes=None, group=None, sync_op=True, use_calc_stream=False):
    group = group or _default_group()
    v = _unwrap(in_tensor)
    if _is_traced(v) and _axis_in_scope(group.axis_name):
        out = jax.lax.all_to_all(v, group.axis_name, split_axis=0, concat_axis=0, tiled=True)
        return Tensor(out)
    n = group.nranks

    def fn(val):
        # [nranks, nranks*k, ...] -> transpose rank-blocks
        blocks = val.reshape((val.shape[0], n, -1) + val.shape[2:])
        return jnp.swapaxes(blocks, 0, 1).reshape(val.shape)

    with no_grad():
        res = apply_op("alltoall_single", fn, [in_tensor])
    if out_tensor is not None:
        out_tensor._value = res._value
        return out_tensor
    return res


def broadcast(tensor, src=0, group=None, sync_op=True, use_calc_stream=False):
    group = group or _default_group()
    v = _unwrap(tensor)
    if _is_traced(v) and _axis_in_scope(group.axis_name):
        # in-program broadcast: select src's value on every rank
        out = jax.lax.all_gather(v, group.axis_name)[group.get_group_rank(src) if src in group.ranks else src]
        return Tensor(out) if isinstance(tensor, Tensor) else out
    idx = group.get_group_rank(src) if src in group.ranks else src

    def fn(val):
        return jnp.broadcast_to(val[idx][None], val.shape)

    with no_grad():
        out = apply_op("broadcast", fn, [tensor])
    if isinstance(tensor, Tensor):
        tensor._value = out._value
        return tensor
    return out


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True, use_calc_stream=False):
    """Rank i receives tensor_list[i] from src.

    Traced (inside shard_map over the group's axis): each rank selects its
    own chunk from the stacked list by ``axis_index`` — the in-program form
    of the reference's scatter kernel.  Eager multi-process: src p2p-sends
    each chunk, others recv theirs.  Single-controller keeps the stacked
    convention (slot i = rank i's chunk)."""
    group = group or _default_group()
    if _axis_in_scope(group.axis_name) and (
            tensor_list and any(_is_traced(_unwrap(t)) for t in tensor_list)
            or _is_traced(_unwrap(tensor))):
        vals = jnp.stack([_unwrap(t) for t in tensor_list], axis=0)
        out = vals[jax.lax.axis_index(group.axis_name)]
        tensor._value = out
        return tensor
    if _process_count() > 1:
        # eager cross-process path: ranks are GLOBAL process ranks (the
        # reference's one-process-per-device model); tensor_list is indexed
        # by group-local position
        me = _process_rank()
        if me == src:
            for local_i, global_r in enumerate(group.ranks):
                if global_r == me:
                    tensor._value = _unwrap(tensor_list[local_i])
                else:
                    send(tensor_list[local_i], dst=global_r, group=group)
        else:
            recv(tensor, src=src, group=group)
        return tensor
    if tensor_list is not None:
        vals = jnp.stack([_unwrap(t) for t in tensor_list], axis=0)
        tensor._value = vals  # stacked: slot i = its chunk
        return tensor
    v = _unwrap(tensor)
    return Tensor(v)


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True, use_calc_stream=False):
    """Collect every rank's tensor at dst (inverse of scatter).

    Traced: lowers to ``all_gather`` over the group axis — every rank
    materializes the stack, dst semantics are a host-side convention (XLA
    collectives are symmetric; discarding on non-dst ranks is free under
    DCE).  Eager multi-process: non-dst ranks p2p-send to dst, which recvs
    in rank order."""
    group = group or _default_group()
    v = _unwrap(tensor)
    if _is_traced(v) and _axis_in_scope(group.axis_name):
        stacked = jax.lax.all_gather(v, group.axis_name)
        if gather_list is not None:
            gather_list.extend(Tensor(stacked[i]) for i in range(group.nranks))
            return gather_list
        return Tensor(stacked)
    if _process_count() > 1:
        # global process ranks, group-local result ordering (see scatter)
        me = _process_rank()
        if me == dst:
            if gather_list is None:
                gather_list = []
            for global_r in group.ranks:
                if global_r == me:
                    gather_list.append(Tensor(v))
                else:
                    chunk = Tensor(jnp.zeros_like(v))
                    recv(chunk, src=global_r, group=group)
                    gather_list.append(chunk)
            return gather_list
        send(tensor, dst=dst, group=group)
        return gather_list
    if gather_list is not None:
        gather_list.extend(Tensor(v[i]) for i in range(v.shape[0]))
        return gather_list
    return Tensor(v)


# ---------------------------------------------------------------------------
# point-to-point
#
# Honest pairing semantics (round-2 verdict #8): every message is keyed by
# (group, src, dst, sequence).  Multi-process transport rides the launch
# CLI's native TCPStore; a recv with no matching send FAILS LOUDLY instead of
# silently delivering someone else's message.  Reference:
# ProcessGroupNCCL::Send/Recv (process_group_nccl.cc:267).
# ---------------------------------------------------------------------------

_p2p_local: dict[tuple, list] = {}          # (gid, src, dst) -> FIFO of values
_p2p_seq: dict[tuple, int] = {}             # (gid, src, dst, "s"/"r") -> counter
_p2p_store_cache: list = [None, False]      # [store, resolved?]
P2P_TIMEOUT = float(os.environ.get("PADDLE_P2P_TIMEOUT", "60"))


def _process_rank() -> int:
    try:
        if jax.process_count() > 1:
            return jax.process_index()
    except Exception:
        pass
    from . import env as _env

    return _env.env_rank()


def _process_count() -> int:
    try:
        if jax.process_count() > 1:
            return jax.process_count()
    except Exception:
        pass
    from . import env as _env

    return _env.env_world_size()


def _p2p_store():
    """Lazy TCPStore client for cross-process p2p payloads (None when
    single-process or no master endpoint is configured)."""
    if _p2p_store_cache[1]:
        return _p2p_store_cache[0]
    _p2p_store_cache[1] = True
    if _process_count() > 1:
        from . import env as _env

        ep = _env.env_master_endpoint()
        if ep:
            from .store import TCPStore

            try:
                _p2p_store_cache[0] = TCPStore(ep[0], ep[1], timeout=10)
            except Exception:
                _p2p_store_cache[0] = None
    return _p2p_store_cache[0]


_BF16_TAG = b"BF16"


def _pack(v) -> bytes:
    import io as _io

    import numpy as _np

    arr = _np.asarray(v)
    tag = b""
    if str(arr.dtype) == "bfloat16":
        # np.save writes bf16 as opaque void; ship as uint16 + tag instead
        arr = arr.view(_np.uint16)
        tag = _BF16_TAG
    buf = _io.BytesIO()
    _np.save(buf, arr, allow_pickle=False)
    return tag + buf.getvalue()


def _unpack(b: bytes):
    import io as _io

    import numpy as _np

    b = bytes(b)
    if b[: len(_BF16_TAG)] == _BF16_TAG:
        return _np.load(_io.BytesIO(b[len(_BF16_TAG):]),
                        allow_pickle=False).view(jnp.bfloat16)
    return _np.load(_io.BytesIO(b), allow_pickle=False)


def send(tensor, dst=0, group=None, sync_op=True, use_calc_stream=False):
    group = group or _default_group()
    v = _unwrap(tensor)
    if _is_traced(v) and _axis_in_scope(group.axis_name):
        # in-program p2p = ppermute ring step; dst interpreted as rank
        n = group.nranks
        out = jax.lax.ppermute(v, group.axis_name, [(i, dst) for i in range(n)])
        return Tensor(out)
    me = _process_rank()  # GLOBAL rank: src/dst arguments are global too
    store = _p2p_store()
    if store is not None:
        seq_key = (group.id, me, dst, "s")
        seq = _p2p_seq.get(seq_key, 0)
        _p2p_seq[seq_key] = seq + 1
        store.set(f"p2p/{group.id}/{me}/{dst}/{seq}", _pack(v))
    else:
        _p2p_local.setdefault((group.id, me, dst), []).append(v)
    return None


def recv(tensor, src=0, group=None, sync_op=True, use_calc_stream=False):
    group = group or _default_group()
    v = _unwrap(tensor)
    if _is_traced(v) and _axis_in_scope(group.axis_name):
        n = group.nranks
        out = jax.lax.ppermute(v, group.axis_name, [(src, i) for i in range(n)])
        return Tensor(out)
    me = _process_rank()  # GLOBAL rank, matching send's key space
    store = _p2p_store()
    if store is not None:
        seq_key = (group.id, src, me, "r")
        seq = _p2p_seq.get(seq_key, 0)
        try:
            payload = store.wait(f"p2p/{group.id}/{src}/{me}/{seq}",
                                 timeout=P2P_TIMEOUT)
        except Exception as e:
            raise RuntimeError(
                f"recv(src={src}) timed out after {P2P_TIMEOUT}s on rank {me} "
                f"(group {group.id}, seq {seq}): no matching send") from e
        # bump the sequence only on success: a timed-out recv must retry the
        # SAME slot or the channel desynchronizes permanently
        _p2p_seq[seq_key] = seq + 1
        try:  # consumed: reclaim the store's memory
            store.delete_key(f"p2p/{group.id}/{src}/{me}/{seq}")
        except Exception:
            pass
        tensor._value = jnp.asarray(_unpack(payload), _unwrap(tensor).dtype)
        return tensor
    q = _p2p_local.get((group.id, src, me))
    if not q:
        pending = sorted(k[:3] for k, lst in _p2p_local.items() if lst)
        raise RuntimeError(
            f"recv(src={src}) on rank {me} (group {group.id}) has no matching "
            f"send; pending sends (gid, src, dst): {pending or 'none'}")
    tensor._value = jnp.asarray(q.pop(0), _unwrap(tensor).dtype)
    return tensor


class _Task:
    def wait(self):
        pass

    def is_completed(self):
        return True


def isend(tensor, dst=0, group=None):
    send(tensor, dst, group)
    return _Task()


def irecv(tensor, src=0, group=None):
    recv(tensor, src, group)
    return _Task()


class P2POp:
    def __init__(self, op, tensor, peer, group=None):
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    tasks = []
    for op in p2p_op_list:
        if op.op in (send, isend, "send", "isend"):
            tasks.append(isend(op.tensor, op.peer, op.group))
        else:
            tasks.append(irecv(op.tensor, op.peer, op.group))
    return tasks


def barrier(group=None):
    jax.effects_barrier()
    for d in jax.local_devices():
        jax.device_put(jnp.zeros(()), d).block_until_ready()


def wait(tensor, group=None, use_calc_stream=True):
    v = _unwrap(tensor)
    if not _is_traced(v):
        v.block_until_ready()


def destroy_process_group(group=None):
    """Drop one group (or all of them) from the registry (reference:
    communication/group.py:171)."""
    global _groups
    if group is None:
        _groups.clear()
        _p2p_store_cache[0], _p2p_store_cache[1] = None, False
    else:
        _groups.pop(group.id, None)


def _store_object_roundtrip(key_prefix, payload, src, group):
    """Publish pickled bytes from src via the TCPStore; everyone else waits.
    Returns the bytes."""
    import pickle

    me = _process_rank()
    store = _p2p_store()
    if store is None:
        # every rank must fail together — a src that "succeeds" alone while
        # receivers raise leaves the job half-past the collective
        raise RuntimeError(
            "object collective: multi-process rendezvous store unavailable "
            "(master endpoint unset or unreachable)")
    seq_key = (group.id, "obj", key_prefix)
    seq = _p2p_seq.get(seq_key, 0)
    _p2p_seq[seq_key] = seq + 1
    key = f"obj/{group.id}/{key_prefix}/{seq}"
    if me == src:
        data = pickle.dumps(payload)
        store.set(key, data)
        return data
    return bytes(store.wait(key, timeout=P2P_TIMEOUT))


def broadcast_object_list(object_list, src=0, group=None):
    """Broadcast picklable objects (reference: communication/broadcast.py:83).
    On non-src ranks the list contents are REPLACED by the src's."""
    import pickle

    group = group or _default_group()
    if _process_count() <= 1:
        return  # single process: src's list is already everyone's list
    data = _store_object_roundtrip("bcast", list(object_list), src, group)
    if _process_rank() != src:
        object_list[:] = pickle.loads(data)


def scatter_object_list(out_object_list, in_object_list=None, src=0, group=None):
    """Scatter one picklable object to each rank (reference:
    communication/scatter.py:91)."""
    import pickle

    group = group or _default_group()
    n = max(_process_count(), 1)
    me = _process_rank()
    if n <= 1:
        # same per-rank slice semantics as the multi-process path at world=1:
        # this rank receives all len(objs)//1 objects, not just the first
        out_object_list[:] = list(in_object_list or [])
        return
    data = _store_object_roundtrip("scatter", list(in_object_list or []),
                                   src, group)
    objs = pickle.loads(data) if me != src else list(in_object_list)
    if len(objs) % n:
        raise ValueError("scatter_object_list: len(in_object_list) must be "
                         "divisible by world size")
    per = len(objs) // n
    out_object_list[:] = objs[me * per:(me + 1) * per]


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Model-parallel linear/embedding with the weight split across ranks
    (reference: fleet/layers/mpu/mp_ops.py:786).  Maps onto the mpu layers:
    'linear' + axis=1 → ColumnParallelLinear, 'linear' + axis=0 →
    RowParallelLinear, 'embedding' → VocabParallelEmbedding."""
    from .fleet import mpu

    if operation == "linear":
        in_f, out_f = int(size[0]), int(size[1])
        if axis == 1:
            layer = mpu.ColumnParallelLinear(
                in_f, out_f, weight_attr=weight_attr,
                has_bias=bias_attr is not False, gather_output=gather_out)
        elif axis == 0:
            layer = mpu.RowParallelLinear(
                in_f, out_f, weight_attr=weight_attr,
                has_bias=bias_attr is not False, input_is_parallel=False)
        else:
            raise ValueError("split(linear) supports axis 0 or 1")
    elif operation == "embedding":
        layer = mpu.VocabParallelEmbedding(int(size[0]), int(size[1]),
                                           weight_attr=weight_attr)
    else:
        raise ValueError(
            f"split supports 'linear' or 'embedding', got {operation!r}")
    return layer(x)


class stream:
    """Namespace mirroring paddle.distributed.communication.stream.* variants."""

    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    reduce_scatter = staticmethod(reduce_scatter)
    alltoall = staticmethod(alltoall)
    broadcast = staticmethod(broadcast)
    send = staticmethod(send)
    recv = staticmethod(recv)


# ---- watchdog instrumentation (reference: every ProcessGroup task is tracked
# by CommTaskManager, comm_task_manager.cc:66; here the host-side eager
# collectives are the trackable unit — see distributed/comm_watchdog.py) ----

def _watched(fn):
    import functools
    import inspect

    from .comm_watchdog import comm_task

    sig = inspect.signature(fn)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        try:  # group may be passed positionally — bind to find it
            group = sig.bind(*args, **kwargs).arguments.get("group")
        except TypeError:
            group = kwargs.get("group")
        with comm_task(fn.__name__, group):
            return fn(*args, **kwargs)

    return wrapper


for _name in (
    "all_reduce", "all_gather", "reduce_scatter", "alltoall", "alltoall_single",
    "broadcast", "reduce", "scatter", "gather", "send", "recv", "barrier",
):
    globals()[_name] = _watched(globals()[_name])
    if hasattr(stream, _name):  # the stream.* aliases must be watched too
        setattr(stream, _name, staticmethod(globals()[_name]))
del _name
