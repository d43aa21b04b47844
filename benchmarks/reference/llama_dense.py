"""Plain float32 reference of the dense Llama-family decoder (Mistral-7B,
Yi-Coder: RMSNorm, rotate-half RoPE, grouped-query causal attention,
SwiGLU, untied head) and of an AdamW step over it.

Straightforward ``jax.numpy`` at ``highest`` matmul precision: no kernels,
no cache, no batching, no chunked head, nothing imported from the program.
Weights are handed in as the benchmark made them (``harness/weights.py``)
and are upcast a layer at a time so that the pass fits beside them.

``lower`` names the precision below the configuration's bfloat16 that the
control is computed in ("int8" or "fp8": both operands of every matmul
rounded, weights per output channel, activations per row; straight-through
in the backward).  ``None`` is the reference itself."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def head_dim(m):
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


# ------------------------------------------------------------ lower precision

def _round(x, axis, lower):
    """``x`` rounded to ``lower`` with one scale along ``axis``."""
    if lower is None:
        return x
    top = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    if lower == "int8":
        scale = top / 127.0
        q = jnp.round(x / scale)
    elif lower == "fp8":
        scale = top / 448.0
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
    else:
        raise ValueError(f"unknown lower precision {lower!r}")
    return x + jax.lax.stop_gradient(q * scale - x)


def mm(x, w, lower=None):
    """x [..., k] @ w [k, n] in float32; under ``lower`` both operands are
    rounded first (x per row, w per output column)."""
    return jnp.matmul(_round(x, -1, lower), _round(w, 0, lower),
                      precision="highest")


# ------------------------------------------------------------------ the model

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def rope_tables(m, positions):
    hd = head_dim(m)
    inv = 1.0 / (m["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, cos, sin):
    """x [s, heads, hd]: rotate-half RoPE."""
    a, b = jnp.split(x, 2, axis=-1)
    return x * cos[:, None, :] + jnp.concatenate([-b, a], -1) * sin[:, None, :]


def attention(m, q, k, v):
    """q [s, nh, hd], k/v [s, nkv, hd] -> [s, nh * hd]; causal; one group
    of query heads at a time so that the scores fit."""
    s, nh, hd = q.shape
    nkv = k.shape[1]
    g = nh // nkv
    qg = q.reshape(s, nkv, g, hd).transpose(1, 2, 0, 3)     # [nkv, g, s, hd]
    kg = k.transpose(1, 0, 2)                               # [nkv, s, hd]
    vg = v.transpose(1, 0, 2)
    keep = jnp.tril(jnp.ones((s, s), bool))

    def one(args):
        qq, kk, vv = args
        sc = jnp.einsum("gqd,kd->gqk", qq, kk,
                        precision="highest") / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->gqd", p, vv, precision="highest")

    out = jax.lax.map(one, (qg, kg, vg))                    # [nkv, g, s, hd]
    return out.transpose(2, 0, 1, 3).reshape(s, nh * hd)


def layer(m, lp, x, cos, sin, lower=None):
    """One decoder block on x [s, h]; ``lp``: this layer's weights in any
    float type."""
    lp = {k: w.astype(F32) for k, w in lp.items()}
    s = x.shape[0]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   head_dim(m))
    xn = rms_norm(x, lp["input_norm"], m["rms_norm_eps"])
    q = rotate(mm(xn, lp["wq"], lower).reshape(s, nh, hd), cos, sin)
    k = rotate(mm(xn, lp["wk"], lower).reshape(s, nkv, hd), cos, sin)
    v = mm(xn, lp["wv"], lower).reshape(s, nkv, hd)
    x = x + mm(attention(m, q, k, v), lp["wo"], lower)
    xn = rms_norm(x, lp["post_norm"], m["rms_norm_eps"])
    gate = mm(xn, lp["w_gate"], lower)
    y = mm(jax.nn.silu(gate) * mm(xn, lp["w_up"], lower), lp["w_down"], lower)
    return x + y


def hidden(m, params, ids, lower=None, remat=False):
    """ids [s] -> last hidden states [s, h].  ``remat`` (training): the
    block is scanned over the stacked layers and recomputed in the backward
    pass, so that one block is compiled and one block's activations held."""
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    cos, sin = rope_tables(m, jnp.arange(ids.shape[0]))
    f = functools.partial(layer, m, lower=lower)
    if remat:
        body = jax.checkpoint(lambda x, lp: (f(lp, x, cos, sin), None))
        return jax.lax.scan(body, x, params["layers"])[0]
    for i in range(m["num_hidden_layers"]):
        x = f({k: w[i] for k, w in params["layers"].items()}, x, cos, sin)
    return x


def head(m, params, x, lower=None):
    w = params.get("lm_head")
    w = params["embed"].T if w is None else w
    xn = rms_norm(x, params["final_norm"].astype(F32), m["rms_norm_eps"])
    return mm(xn, w.astype(F32), lower)


# ------------------------------------------------------------ serving's check

@functools.partial(jax.jit, static_argnames=("m_key", "lower"))
def _layer_jit(m_key, lp, x, cos, sin, lower):
    return layer(dict(m_key), lp, x, cos, sin, lower)


@functools.partial(jax.jit, static_argnames=("m_key", "lower"))
def _head_jit(m_key, params_head, x, lower):
    return head(dict(m_key), params_head, x, lower)


def _key(m):
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool, str))
                        or v is None))


def logits_at(m, params, ids, at, lower=None, pad_to=512):
    """float32 logits rows ``at`` of one sequence ``ids``: a layer at a
    time (each jitted once per padded length), causal, so right padding
    changes nothing before it."""
    ids = np.asarray(ids, np.int32)
    n = -(-ids.size // pad_to) * pad_to
    padded = np.zeros(n, np.int32)
    padded[:ids.size] = ids
    key = _key(m)
    x = jnp.take(params["embed"], jnp.asarray(padded), axis=0).astype(F32)
    cos, sin = rope_tables(m, jnp.arange(n))
    for i in range(m["num_hidden_layers"]):
        lp = {k: w[i] for k, w in params["layers"].items()}
        x = _layer_jit(key, lp, x, cos, sin, lower)
    rows = jnp.take(x, jnp.asarray(np.asarray(at, np.int32)), axis=0)
    heads = {k: params[k] for k in ("lm_head", "embed", "final_norm")
             if k in params and (k != "embed" or "lm_head" not in params)}
    return _head_jit(key, heads, rows, lower)


def served_gap(m, params, prompt, served, lower=None):
    """For one finished request: at each generated position, how far the
    served token's reference logit lies below the reference's best
    (``lower=None``), or how far the token that the lower precision puts
    first lies below it (the control).  Returns the gaps, one a token."""
    prompt, served = np.asarray(prompt), np.asarray(served)
    ids = np.concatenate([prompt, served[:-1]])
    at = prompt.size - 1 + np.arange(served.size)
    ref = logits_at(m, params, ids, at)
    if lower is None:
        picked = jnp.asarray(served)
    else:
        picked = jnp.argmax(logits_at(m, params, ids, at, lower), axis=-1)
    mine = jnp.take_along_axis(ref, picked[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    return np.asarray(ref.max(axis=-1) - mine)


# ----------------------------------------------------------- training's check

def token_loss_sum(m, params, ids, labels, lower=None):
    """Summed next-token cross entropy of one row ids/labels [s]."""
    x = hidden(m, params, ids, lower, remat=True)
    logp = jax.nn.log_softmax(head(m, params, x, lower), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("m_key", "lower"),
                   donate_argnums=(2,))
def _row_grad(m_key, params, acc, ids, labels, lower):
    loss, g = jax.value_and_grad(
        lambda p: token_loss_sum(dict(m_key), p, ids, labels, lower))(params)
    return loss, jax.tree_util.tree_map(jnp.add, acc, g)


def loss_and_grads(m, params, ids, labels, lower=None, rows=None):
    """Mean loss and its gradient over the batch ids/labels [b, s], one row
    at a time.  ``rows`` (a fault for the tests) keeps only those rows and
    takes the mean over them."""
    rows = range(ids.shape[0]) if rows is None else rows
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    total = jnp.zeros((), F32)
    for r in rows:
        loss, acc = _row_grad(_key(m), params, acc, jnp.asarray(ids[r]),
                              jnp.asarray(labels[r]), lower)
        total = total + loss
    n = len(rows) * ids.shape[1]
    return total / n, jax.tree_util.tree_map(lambda g: g / n, acc)


def leaf_norms(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(p.key) for p in path):
            float(jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))))
            for path, x in flat}


@functools.partial(jax.jit, static_argnames=("hp",), donate_argnums=(0, 1, 2))
def _adamw(params, mom, var, grads, step, hp):
    hp = dict(hp)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-6))
    b1, b2 = hp["beta1"], hp["beta2"]
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, m_, v_, g):
        g = g * scale
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * g * g
        step_ = (m2 / c1) / (jnp.sqrt(v2 / c2) + hp["eps"])
        return p * (1 - hp["lr"] * hp["weight_decay"]) - hp["lr"] * step_, m2, v2

    out = jax.tree_util.tree_map(upd, params, mom, var, grads)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), scale


def train_readings(m, make_params0, batches, hp, lower=None,
                   rows=None) -> dict:
    """Follow ``len(batches)`` AdamW steps from ``make_params0()`` (any
    float type; upcast, and made a second time at the end rather than kept,
    so that the pass fits) on ``batches`` [(ids, labels)].  Returns each step's
    loss, the per-leaf norm of the first gradient as the optimizer gets it
    (after global-norm clipping) and before clipping, and the per-leaf norm
    of the parameters' change after the last step."""
    hp_key = tuple(sorted(hp.items()))
    params = jax.tree_util.tree_map(lambda w: w.astype(F32),
                                    make_params0())
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    var = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {"loss": []}
    for step, (ids, labels) in enumerate(batches, 1):
        loss, grads = loss_and_grads(m, params, ids, labels, lower, rows)
        out["loss"].append(float(loss))
        if step == 1:
            out["grad_raw"] = leaf_norms(grads)
        params, mom, var, scale = _adamw(params, mom, var, grads,
                                         float(step), hp_key)
        if step == 1:
            out["grad"] = {k: v * float(scale)
                           for k, v in out["grad_raw"].items()}
        del grads
    del mom, var
    out["change"] = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b.astype(F32), params, make_params0()))
    return out
