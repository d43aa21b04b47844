"""Weights from the seed, made by the benchmark (not by the program) in
the type they are served or trained in, on the device, in one jitted call.
The tree has the layout ``paddle_tpu.models.llama`` takes: layer matrices
stacked over a leading layer dimension."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import work

STD = 0.02


def shapes(m: dict) -> dict:
    h, i, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    L, hd = m["num_hidden_layers"], work.head_dim(m)
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    tree = {
        "embed": (v, h), "final_norm": (h,),
        "layers": {"input_norm": (L, h), "post_norm": (L, h),
                   "wq": (L, h, q), "wk": (L, h, kv), "wv": (L, h, kv),
                   "wo": (L, q, h), "w_gate": (L, h, i), "w_up": (L, h, i),
                   "w_down": (L, i, h)},
    }
    if not m.get("tie_word_embeddings"):
        tree["lm_head"] = (h, v)
    return tree


def build(m: dict, key, dtype=jnp.bfloat16) -> dict:
    """Traced body: normal(0, 0.02) matrices, norm gains of one."""
    is_shape = lambda s: isinstance(s, tuple)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(m), is_leaf=is_shape)
    keys = jax.random.split(key, len(flat))
    leaves = []
    for k, (path, shape) in zip(keys, flat):
        if str(path[-1].key).endswith("norm"):
            leaves.append(jnp.ones(shape, dtype))
        else:
            leaves.append((jax.random.normal(k, shape, jnp.float32)
                           * STD).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_params(m: dict, seed: int, out_shardings=None) -> dict:
    fn = jax.jit(lambda k: build(m, k), out_shardings=out_shardings)
    return fn(jax.random.key(int(seed)))
