"""Weights from the seed for ``paddle_tpu.models.olmo_hybrid``: the tree that
program takes (its ``param_shapes``: ``linear`` and ``full`` leaves stacked
over the layers of their kind, in layer order), made by the benchmark on the
device in one jitted call.  normal(0, 0.02) matrices in the configuration's
type, norm gains of one; the two leaves of the decay, kept in float32, over
Gated DeltaNet's own initial ranges (the configuration's ``assumed``):
``A_log`` = log U(1, 16), ``dt_bias`` the inverse softplus of a step drawn
log-uniformly from [1e-3, 1e-1]."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

STD = 0.02
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def shapes(m: dict) -> dict:
    from paddle_tpu.models import olmo_hybrid

    return olmo_hybrid.param_shapes(olmo_hybrid.config_from_dict(m))


def build(m: dict, key) -> dict:
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
        elif name == "A_log":
            leaves.append(jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0)))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            leaves.append(dt + jnp.log(-jnp.expm1(-dt)))
        else:
            leaves.append((jax.random.normal(k, shape, jnp.float32)
                           * STD).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_params(m: dict, seed: int, out_shardings=None) -> dict:
    fn = jax.jit(lambda k: build(m, k), out_shardings=out_shardings)
    return fn(jax.random.key(int(seed)))
