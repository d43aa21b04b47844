"""Weights from the seed for ``paddle_tpu.models.laguna``: the tree that
program takes (its ``param_shapes``: ``layers`` grouped into ``lead``,
``period`` stacked over the repetitions, ``rest``), made by the benchmark
on the device in one jitted call.  normal(0, 0.02) matrices in the
configuration's type, the router in float32, norm gains of one.
``absent_router_kept`` is the share of the absent experts' router columns
that are kept, the rest set to nought: under one, a router that knows fewer
of the experts this chip does not hold, so that more of a token's choices
fall on the held ones and fill several chunks of their grouped products (the
check's second start; an expert whose logits are all nought is never among
the chosen)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

STD = 0.02
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def shapes(m: dict) -> dict:
    from paddle_tpu.models import laguna

    return laguna.param_shapes(laguna.LagunaConfig.from_dict(m))


def build(m: dict, key, absent_router_kept: float = 1.0) -> dict:
    """Traced body."""
    dtype = DTYPES[m.get("torch_dtype", "bfloat16")]
    is_shape = lambda s: isinstance(s, tuple)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(m), is_leaf=is_shape)
    leaves = []
    for k, (path, shape) in zip(jax.random.split(key, len(flat)), flat):
        name = str(path[-1].key)
        if name.endswith("norm"):
            leaves.append(jnp.ones(shape, dtype))
        else:
            w = jax.random.normal(k, shape, jnp.float32) * STD
            if name == "router" and absent_router_kept != 1.0:
                lo, hi = m.get("experts_held") or (0, m["num_experts"])
                e = jnp.arange(m["num_experts"])
                absent = jnp.where(e < lo, e, e - (hi - lo))    # its rank
                kept = absent < absent_router_kept * (m["num_experts"]
                                                      - (hi - lo))
                w = jnp.where((e >= lo) & (e < hi) | kept, w, 0.0)
            leaves.append(w if name == "router" else w.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_params(m: dict, seed: int, out_shardings=None,
                absent_router_kept: float = 1.0) -> dict:
    fn = jax.jit(lambda k: build(m, k, absent_router_kept),
                 out_shardings=out_shardings)
    return fn(jax.random.key(int(seed)))
