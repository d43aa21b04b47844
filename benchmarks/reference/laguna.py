"""Plain float32 reference of the Laguna decoder (poolside Laguna-XS.2:
layers of two kinds of attention with different query-head counts, partial
and yarn rope, a per-head output gate, a leading dense SwiGLU layer and
sigmoid-routed experts beside one shared expert) and of an AdamW step over
it.

Straightforward ``jax.numpy`` at ``highest`` matmul precision: no kernels,
no batching, no chunked head, nothing imported from the program.  What the
published config leaves open is marked *assumed* where it is decided.
Weights come in the tree the program takes (``harness/weights_laguna.py``):
``layers`` holds ``lead`` (layers before the pattern repeats), ``period``
(one entry a position of the repeating pattern, stacked over the
repetitions) and ``rest`` (a last partial repetition), each entry keyed by
its number as a string.

``m`` is a configuration's ``model`` group: the published keys, plus
``experts_held`` ``[lo, hi)``, the experts this chip holds.  Every token is
routed over all ``num_experts``; only the held experts' terms are added,
exactly as the program leaves the absent experts' terms out, and with a
share held the routing weights carry no gradient.

``lower`` ("int8", "fp8") and ``rows`` are the controls of
``llama_dense.train_readings``: a precision below bfloat16, and a batch cut
to some of its rows."""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from .llama_dense import _adamw, _key, leaf_norms, mm, rms_norm

F32 = jnp.float32
QUERY_BLOCK = 512


# ------------------------------------------------------------------ the plan

def layer_plan(m, params):
    """[(layer's weights as a function of the tree, layer number)] in the
    order the layers run, read off the tree's own grouping."""
    groups = params["layers"]
    lead = [groups["lead"][str(i)] for i in range(len(groups.get("lead", {})))]
    period = [groups["period"][str(i)]
              for i in range(len(groups.get("period", {})))]
    rest = [groups["rest"][str(i)] for i in range(len(groups.get("rest", {})))]
    out = list(lead)
    if period:
        n = jax.tree_util.tree_leaves(period[0])[0].shape[0]
        for r in range(n):
            out += [jax.tree_util.tree_map(lambda w: w[r], lp)
                    for lp in period]
    out += rest
    if len(out) != m["num_hidden_layers"]:
        raise ValueError(f"the tree holds {len(out)} layers, the "
                         f"configuration {m['num_hidden_layers']}")
    return out


def experts_held(m):
    lo, hi = m.get("experts_held") or (0, m["num_experts"])
    return int(lo), int(hi)


# ---------------------------------------------------------------------- rope

def rope_tables(rp, head_dim, positions):
    """(cos, sin) [s, r] for one layer kind's ``rope_parameters`` entry:
    ``r = partial_rotary_factor * head_dim`` rotary dimensions; yarn blends
    each inverse frequency between itself and itself / factor along the
    linear ramp between the correction dimensions of beta_fast and
    beta_slow, and scales cos and sin by ``attention_factor``."""
    r = int(round(head_dim * rp.get("partial_rotary_factor", 1)))
    base = float(rp["rope_theta"])
    inv = 1.0 / (base ** (jnp.arange(0, r, 2, dtype=F32) / r))
    scale = 1.0
    if rp.get("rope_type", "default") == "yarn":
        factor = float(rp["factor"])
        orig = rp["original_max_position_embeddings"]

        def correction_dim(rotations):
            return (r * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(rp["beta_slow"])), r - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(r // 2, dtype=F32) - low) / (high - low),
                        0.0, 1.0)
        inv = (inv / factor) * ramp + inv * (1.0 - ramp)
        scale = float(rp.get("attention_factor")
                      or 0.1 * math.log(factor) + 1.0)
    elif rp.get("rope_type", "default") != "default":
        raise ValueError(f"unknown rope_type {rp['rope_type']!r}")
    ang = positions.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rotate(x, cos, sin):
    """x [s, heads, hd]: rotate-half rope on the first ``cos.shape[-1]``
    dimensions, the rest passed through (*assumed*: the first ones, the
    Hugging Face convention for a partial rotary factor)."""
    r = cos.shape[-1]
    xr, rest = x[..., :r], x[..., r:]
    a, b = jnp.split(xr, 2, axis=-1)
    xr = xr * cos[:, None, :] + jnp.concatenate([-b, a], -1) * sin[:, None, :]
    return jnp.concatenate([xr, rest], axis=-1)


# ----------------------------------------------------------------- attention

def attention(q, k, v, window=None):
    """q [s, nh, hd], k/v [s, nkv, hd] -> [s, nh, hd]; query head ``h`` on
    KV head ``h // (nh / nkv)``; causal, and under ``window`` a position
    sees itself and the ``window - 1`` before it (*assumed*).  One KV head's
    group and one block of queries at a time, so that the scores fit."""
    s, nh, hd = q.shape
    nkv = k.shape[1]
    g = nh // nkv
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    qg = q.reshape(s // bq, bq, nkv, g, hd).transpose(2, 0, 3, 1, 4)
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)     # [nkv, s, hd]
    cols = jnp.arange(s)[None, :]

    def group(args):
        qs, kk, vv = args                                   # qs [nb,g,bq,hd]

        @jax.checkpoint     # the backward pass holds one block's scores
        def block(args):
            qq, first = args
            rows = first + jnp.arange(bq)[:, None]
            keep = cols <= rows
            if window is not None:
                keep &= rows - cols < window
            sc = jnp.einsum("gqd,kd->gqk", qq, kk,
                            precision="highest") / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
            return jnp.einsum("gqk,kd->gqd", p, vv, precision="highest")

        return jax.lax.map(block, (qs, jnp.arange(s // bq) * bq))

    out = jax.lax.map(group, (qg, kg, vg))          # [nkv, nb, g, bq, hd]
    return out.transpose(1, 3, 0, 2, 4).reshape(s, nh, hd)


# ----------------------------------------------------------------------- mlp

def swiglu(x, w_gate, w_up, w_down, lower):
    """*assumed*: SiLU (the config has no ``hidden_act``)."""
    return mm(jax.nn.silu(mm(x, w_gate, lower)) * mm(x, w_up, lower),
              w_down, lower)


def routing(m, b, router, lower=None):
    """(weights [T, K], experts [T, K]) of every token of one sequence over
    all ``num_experts``.  *assumed*: sigmoid scores (the convention that
    ``moe_routed_scaling_factor`` comes from), the K largest normalised to
    sum to one (``norm_topk_prob`` of the family's sibling Laguna-S-2.1) and
    scaled by the routed scaling factor.  *assumed*, under
    ``router_selection: "sequence_standard"``: the K are chosen by each
    logit's standard score over the sequence (less the expert's mean logit
    there, over its deviation: what a per-expert selection bias trained for
    load balance does, in closed form; held constant in the backward pass),
    the weights still from the scores as they are."""
    logits = mm(b, router, lower)
    scores = jax.nn.sigmoid(logits)
    chosen_by = scores
    if m.get("router_selection", "scores") == "sequence_standard":
        centred = logits - jnp.mean(logits, axis=0, keepdims=True)
        chosen_by = jax.lax.stop_gradient(centred / jnp.sqrt(
            jnp.mean(jnp.square(centred), axis=0, keepdims=True) + 1e-12))
    _, experts = jax.lax.top_k(chosen_by, m["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, experts, axis=-1)
    w = m["moe_routed_scaling_factor"] * top / jnp.sum(top, -1, keepdims=True)
    return w, experts


def sparse_mlp(m, lp, b, lower=None):
    """Shared expert (*assumed* ungated) + the held experts' weighted
    outputs; router weights on the output."""
    lo, hi = experts_held(m)
    w, experts = routing(m, b, lp["router"], lower)
    if hi - lo < m["num_experts"]:
        # a share of the experts: its terms alone are not the router's
        # gradient (they tell held experts from absent ones); left out
        w = jax.lax.stop_gradient(w)
    # [T, held]: a token's weight on each held expert, 0 where not chosen
    held = jnp.arange(lo, hi)
    on = jnp.sum(jnp.where(experts[:, :, None] == held[None, None, :],
                           w[:, :, None], 0.0), axis=1)

    @jax.checkpoint         # the backward pass holds one expert's rows
    def expert(b, e_gate, e_up, e_down, we):
        return we[:, None] * swiglu(b, e_gate, e_up, e_down, lower)

    def one(total, args):
        return total + expert(b, *args), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(b),
                             (lp["e_gate"], lp["e_up"], lp["e_down"], on.T))
    return swiglu(b, lp["s_gate"], lp["s_up"], lp["s_down"], lower) + routed


# --------------------------------------------------------------------- layer

def layer(m, i, lp, x, tables, lower=None):
    """Decoder block number ``i`` on x [s, h]."""
    lp = {k: w.astype(F32) for k, w in lp.items()}
    s = x.shape[0]
    kind = m["layer_types"][i]
    nh = m["num_attention_heads_per_layer"][i]
    nkv, hd, eps = m["num_key_value_heads"], m["head_dim"], m["rms_norm_eps"]
    cos, sin = tables[kind]
    # *assumed*: no q/k norm (the config has no key for one); no biases
    a = rms_norm(x, lp["input_norm"], eps)
    q = rotate(mm(a, lp["wq"], lower).reshape(s, nh, hd), cos, sin)
    k = rotate(mm(a, lp["wk"], lower).reshape(s, nkv, hd), cos, sin)
    v = mm(a, lp["wv"], lower).reshape(s, nkv, hd)
    window = m["sliding_window"] if kind == "sliding_attention" else None
    o = attention(q, k, v, window)
    if m.get("gating"):
        # *assumed*: ``gating: true`` is the sibling's ``"per-head"``: one
        # sigmoid gate a query head, from the normed input
        o = o * jax.nn.sigmoid(mm(a, lp["wg"], lower))[:, :, None]
    x = x + mm(o.reshape(s, nh * hd), lp["wo"], lower)
    b = rms_norm(x, lp["post_norm"], eps)
    if m["mlp_layer_types"][i] == "dense":
        return x + swiglu(b, lp["w_gate"], lp["w_up"], lp["w_down"], lower)
    return x + sparse_mlp(m, lp, b, lower)


def hidden(m, params, ids, lower=None):
    """ids [s] -> last hidden states [s, h]; each block recomputed in the
    backward pass so that one block's activations are held."""
    x = jnp.take(params["embed"], ids, axis=0).astype(F32)
    pos = jnp.arange(ids.shape[0])
    tables = {kind: rope_tables(rp, m["head_dim"], pos)
              for kind, rp in m["rope_parameters"].items()
              if isinstance(rp, dict)}
    for i, lp in enumerate(layer_plan(m, params)):
        x = jax.checkpoint(functools.partial(layer, m, i, tables=tables,
                                             lower=lower))(lp, x)
    return x


def logits(m, params, ids, lower=None):
    x = hidden(m, params, ids, lower)
    xn = rms_norm(x, params["final_norm"].astype(F32), m["rms_norm_eps"])
    return mm(xn, params["lm_head"].astype(F32), lower)


# ----------------------------------------------------------- training's check

def token_loss_sum(m, params, ids, labels, lower=None):
    """Summed next-token cross entropy of one row ids/labels [s]; *assumed*:
    no auxiliary loss (the config names no coefficient)."""
    logp = jax.nn.log_softmax(logits(m, params, ids, lower), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _model_key(m):
    """``m`` as a hashable: scalars by ``_key``, lists and groups by their
    JSON text."""
    nested = tuple(sorted((k, json.dumps(v, sort_keys=True))
                          for k, v in m.items()
                          if isinstance(v, (list, tuple, dict))))
    return _key(m), nested


def _model_of(key):
    flat, nested = key
    return dict(flat, **{k: json.loads(v) for k, v in nested})


@functools.partial(jax.jit, static_argnames=("m_key", "lower"),
                   donate_argnums=(2,))
def _row_grad(m_key, params, acc, ids, labels, lower):
    loss, g = jax.value_and_grad(
        lambda p: token_loss_sum(_model_of(m_key), p, ids, labels,
                                 lower))(params)
    return loss, jax.tree_util.tree_map(jnp.add, acc, g)


def loss_and_grads(m, params, ids, labels, lower=None, rows=None):
    """Mean loss and its gradient over the batch ids/labels [b, s], one row
    at a time.  ``rows`` (a fault for the tests) keeps only those rows and
    takes the mean over them."""
    rows = range(ids.shape[0]) if rows is None else rows
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    total = jnp.zeros((), F32)
    key = _model_key(m)
    for r in rows:
        loss, acc = _row_grad(key, params, acc, jnp.asarray(ids[r]),
                              jnp.asarray(labels[r]), lower)
        total = total + loss
    n = len(rows) * ids.shape[1]
    return total / n, jax.tree_util.tree_map(lambda g: g / n, acc)


def train_readings(m, make_params0, batches, hp, lower=None,
                   rows=None) -> dict:
    """``llama_dense.train_readings`` for this model: each step's loss, the
    per-leaf norm of the first gradient after and before global-norm
    clipping, and the per-leaf norm of the parameters' change after the
    last step.  The AdamW moments are made after the first gradient and
    kept on the host between steps, so that the pass fits one chip."""
    hp_key = tuple(sorted(hp.items()))
    params = jax.tree_util.tree_map(lambda w: w.astype(F32), make_params0())
    out = {"loss": []}
    held = None             # (m, v) on the host while gradients are taken
    for step, (ids, labels) in enumerate(batches, 1):
        loss, grads = loss_and_grads(m, params, ids, labels, lower, rows)
        out["loss"].append(float(loss))
        if step == 1:
            out["grad_raw"] = leaf_norms(grads)
            mom = jax.tree_util.tree_map(jnp.zeros_like, params)
            var = jax.tree_util.tree_map(jnp.zeros_like, params)
        else:
            mom, var = jax.device_put(held)
        params, mom, var, scale = _adamw(params, mom, var, grads,
                                         float(step), hp_key)
        if step == 1:
            out["grad"] = {k: v * float(scale)
                           for k, v in out["grad_raw"].items()}
        # params, the summed gradient and a row's activations fill the chip
        # at 8,192 positions: m and v wait on the host
        held = jax.device_get((mom, var)) if step < len(batches) else None
        del grads, mom, var
    out["change"] = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b.astype(F32), params, make_params0()))
    return out
