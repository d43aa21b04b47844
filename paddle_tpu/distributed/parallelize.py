"""Single-card → distributed conversion: the ``paddle.distributed.parallelize``
plan API (reference: python/paddle/distributed/auto_parallel/intermediate/
parallelize.py:51, tensor_parallel.py:95-638, pipeline_parallel.py:30).

TPU-native mapping: a plan marks parameters with DTensor placements
(``shard_tensor`` → NamedSharding on the mesh's ``mp`` axis) and registers
redistribute hooks on the layer; GSPMD propagates the shardings and inserts
the all-gathers/reduce-scatters the reference's per-plan hooks issue
explicitly.  Pipeline split points are recorded as annotations consumed by
the fleet pipeline engines (fleet/pipeline.py)."""

from __future__ import annotations

import re
from enum import Enum

from .auto_parallel.api import shard_optimizer, shard_tensor
from .auto_parallel.placement import Replicate, Shard

__all__ = [
    "PlanBase", "ColWiseParallel", "RowWiseParallel", "PrepareLayerInput",
    "PrepareLayerOutput", "SequenceParallelBegin", "SequenceParallelEnd",
    "SequenceParallelEnable", "SequenceParallelDisable", "SplitPoint",
    "ParallelMode", "parallelize",
]


class SplitPoint(Enum):
    """Pipeline stage boundary marker (pipeline_parallel.py:30)."""
    BEGINNING = 0
    END = 1


class ParallelMode:
    """Parallelism classification constants (reference:
    auto_parallel/static/operators/common.py:64)."""
    DataParallel = "auto_parallel/data_parallel"
    TensorParallel = "auto_parallel/tensor_parallel"
    PipelineParallel = "auto_parallel/pipeline_parallel"
    MoEParallel = "auto_parallel/moe_parallel"


def _mp_axis(mesh):
    """Index + name of the tensor-parallel mesh axis ('mp' by convention,
    else the last axis)."""
    names = list(mesh.dim_names)
    name = "mp" if "mp" in names else names[-1]
    return names.index(name), name


def _placements(mesh, tensor_dim, mesh_axis):
    pl = [Replicate()] * mesh.ndim
    pl[mesh_axis] = Shard(tensor_dim)
    return pl


class PlanBase:
    """One sharding action applied to a matched sublayer
    (tensor_parallel.py:95)."""

    def apply(self, layer, process_mesh, shard_param_list):
        raise NotImplementedError


def _shard_param(layer, pname, mesh, tensor_dim):
    p = layer._parameters.get(pname)
    if p is None:
        return
    ax, _ = _mp_axis(mesh)
    shard_tensor(p, mesh, _placements(mesh, tensor_dim, ax))


class ColWiseParallel(PlanBase):
    """Split weight on its second dim / bias on its first
    (tensor_parallel.py:103; Linear weight is [in, out] in paddle layout so
    the output features shard)."""

    def __init__(self, gather_output: bool = False):
        self.gather_output = gather_output

    def apply(self, layer, process_mesh, shard_param_list=None):
        targets = shard_param_list or ["weight", "bias"]
        if "weight" in targets and layer._parameters.get("weight") is not None:
            w = layer._parameters["weight"]
            _shard_param(layer, "weight", process_mesh,
                         1 if len(w.shape) == 2 else 0)
        if "bias" in targets:
            _shard_param(layer, "bias", process_mesh, 0)
        if self.gather_output:
            from .auto_parallel.api import reshard

            def gather(lyr, inputs, out):
                t = out[0] if isinstance(out, (tuple, list)) else out
                if getattr(t, "dist_attr", None) is not None:
                    r = reshard(t, process_mesh,
                                [Replicate()] * process_mesh.ndim)
                    return (r,) + tuple(out[1:]) if isinstance(out, (tuple, list)) else r
                return out

            layer.register_forward_post_hook(gather)
        return layer


class RowWiseParallel(PlanBase):
    """Split weight on its first dim (tensor_parallel.py:211); the matching
    input is expected feature-sharded, partial sums psum on the way out
    (GSPMD inserts the reduce when the sharded dims contract)."""

    def __init__(self, is_input_parallel: bool = True):
        self.is_input_parallel = is_input_parallel

    def apply(self, layer, process_mesh, shard_param_list=None):
        targets = shard_param_list or ["weight"]
        if "weight" in targets:
            _shard_param(layer, "weight", process_mesh, 0)
        return layer


class PrepareLayerInput(PlanBase):
    """Run a user fn over the layer inputs (tensor_parallel.py:308); fn is
    called as fn(process_mesh) → hook(layer, inputs)."""

    def __init__(self, fn=None):
        self.fn = fn

    def apply(self, layer, process_mesh, shard_param_list=None):
        if self.fn is not None:
            layer.register_forward_pre_hook(self.fn(process_mesh))
        return layer


class PrepareLayerOutput(PlanBase):
    def __init__(self, fn=None):
        self.fn = fn

    def apply(self, layer, process_mesh, shard_param_list=None):
        if self.fn is not None:
            layer.register_forward_post_hook(self.fn(process_mesh))
        return layer


class _SPBase(PlanBase):
    """Sequence-parallel hooks: redistribute activations between
    Shard(seq_dim) and Replicate around the marked layer.  The reference
    assumes [b, s, h] activations (tensor_parallel.py:418)."""

    seq_dim = 1

    def _to_seq_sharded(self, mesh):
        from .auto_parallel.api import reshard

        ax, _ = _mp_axis(mesh)

        def hook_val(t):
            if getattr(t, "dist_attr", None) is not None:
                return reshard(t, mesh, _placements(mesh, self.seq_dim, ax))
            return t

        return hook_val

    def _to_replicated(self, mesh):
        from .auto_parallel.api import reshard

        def hook_val(t):
            if getattr(t, "dist_attr", None) is not None:
                return reshard(t, mesh, [Replicate()] * mesh.ndim)
            return t

        return hook_val

    @staticmethod
    def _map_out(out, fn):
        if isinstance(out, (tuple, list)):
            return type(out)(fn(o) for o in out)
        return fn(out)


class SequenceParallelBegin(_SPBase):
    """Enter the SP region: outputs become seq-sharded
    (tensor_parallel.py:418)."""

    def __init__(self, need_transpose: bool = True):
        self.need_transpose = need_transpose

    def apply(self, layer, process_mesh, shard_param_list=None):
        fn = self._to_seq_sharded(process_mesh)
        layer.register_forward_post_hook(
            lambda lyr, inputs, out: self._map_out(out, fn))
        return layer


class SequenceParallelEnd(_SPBase):
    """Leave the SP region: inputs gathered back to replicated
    (tensor_parallel.py:470)."""

    def __init__(self, need_transpose: bool = True):
        self.need_transpose = need_transpose

    def apply(self, layer, process_mesh, shard_param_list=None):
        fn = self._to_replicated(process_mesh)
        layer.register_forward_pre_hook(
            lambda lyr, inputs: tuple(fn(i) for i in inputs))
        return layer


class SequenceParallelEnable(_SPBase):
    """Run this layer itself sequence-parallel (tensor_parallel.py:522):
    seq-shard its input, keep its output seq-sharded."""

    def apply(self, layer, process_mesh, shard_param_list=None):
        fn = self._to_seq_sharded(process_mesh)
        layer.register_forward_pre_hook(
            lambda lyr, inputs: tuple(fn(i) for i in inputs))
        return layer


class SequenceParallelDisable(_SPBase):
    """Opt this layer out of the surrounding SP region
    (tensor_parallel.py:579)."""

    def __init__(self, need_transpose: bool = True):
        self.need_transpose = need_transpose

    def apply(self, layer, process_mesh, shard_param_list=None):
        gather = self._to_replicated(process_mesh)
        scatter = self._to_seq_sharded(process_mesh)
        layer.register_forward_pre_hook(
            lambda lyr, inputs: tuple(gather(i) for i in inputs))
        layer.register_forward_post_hook(
            lambda lyr, inputs, out: self._map_out(out, scatter))
        return layer


def _match_layers(model, pattern):
    """Sublayers whose qualified name matches (exact, or regex fullmatch —
    the reference accepts regex keys in parallelize_plan)."""
    found = []
    for name, sub in model.named_sublayers(include_self=False):
        if name == pattern or re.fullmatch(pattern, name):
            found.append((name, sub, None))
    if found:
        return found
    # param-targeted key: "<layer>.weight" / "<layer>.bias"
    for suffix in ("weight", "bias"):
        if pattern.endswith("." + suffix):
            base = pattern[: -(len(suffix) + 1)]
            for name, sub in model.named_sublayers(include_self=False):
                if name == base or re.fullmatch(base, name):
                    found.append((name, sub, [suffix]))
    return found


def parallelize(model, optimizer=None, mesh=None, config=None):
    """parallelize.py:51 — apply dp/mp/pp configs to a single-card model.

    config keys: ``mp_config`` {"parallelize_plan": {name_or_regex: plan}},
    ``dp_config`` {"sharding_level": 0..3}, ``pp_config`` {"split_spec": ...}.
    Returns (model, optimizer)."""
    from .auto_parallel import get_mesh

    mesh = mesh or get_mesh()
    if mesh is None:
        raise ValueError(
            "parallelize needs a mesh: pass mesh= or call "
            "dist.auto_parallel.set_mesh first")
    config = config or {}

    mp_cfg = config.get("mp_config") or {}
    plan_map = mp_cfg.get("parallelize_plan") or {}
    for pattern, plan in plan_map.items():
        plans = plan if isinstance(plan, (list, tuple)) else [plan]
        matched = _match_layers(model, pattern)
        for _, sub, shard_param_list in matched:
            for p in plans:
                p.apply(sub, mesh, shard_param_list)

    pp_cfg = config.get("pp_config") or {}
    if pp_cfg.get("split_spec"):
        # recorded as a validated ANNOTATION: the executing engines
        # (fleet/pipeline.py) take explicit per-stage functions, so the
        # split request is carried on the model for the recipe layer to
        # consume — validated here so a typo'd layer name fails loudly
        spec = pp_cfg["split_spec"]
        if isinstance(spec, dict):
            known = {name for name, _ in model.named_sublayers()}
            for lname in spec:
                if not any(n == lname or n.startswith(lname + ".")
                           for n in known):
                    raise ValueError(
                        f"pp_config split_spec names unknown layer {lname!r};"
                        f" model layers: {sorted(known)[:10]}...")
        model._pp_split_spec = spec
        model._pp_global_spec = pp_cfg.get("global_spec")

    dp_cfg = config.get("dp_config") or {}
    level = dp_cfg.get("sharding_level")
    if optimizer is not None and level:
        from .auto_parallel.api import (ShardingStage1, ShardingStage2,
                                        ShardingStage3)

        stage = {1: ShardingStage1, 2: ShardingStage2, 3: ShardingStage3}[int(level)]
        names = list(mesh.dim_names)
        dp_name = "dp" if "dp" in names else names[0]
        optimizer = shard_optimizer(optimizer, stage(dp_name, mesh))
    return model, optimizer
