"""Plain float32 reference of the Olmo-Hybrid decoder (allenai
Olmo-Hybrid-7B): Gated DeltaNet linear-attention layers (arXiv:2412.06464,
negative eigenvalues allowed as in arXiv:2411.12537) three to one
full-attention layer, Olmo 2/3 block order (the norm on a sublayer's
output), SwiGLU, untied head.

Straightforward ``jax.numpy`` at ``highest`` matmul precision; nothing is
imported from the program.  The recurrence is computed **token by token** in
a ``lax.scan`` over the paper's own ``S`` of [d_v, d_k] a head::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

never in the chunked form and never over the transposed state the program
keeps, so that the two share no formulation.  Weights are handed in as the
benchmark made them (``harness/weights_olmo_hybrid.py``: ``linear`` and
``full`` leaves stacked over the layers of their kind, in layer order) and
are upcast a layer at a time so that the pass fits beside them.

``lower`` names what the control is computed in: ``"fp8"`` rounds both
operands of every matmul with a weight (weights per output channel,
activations per row); ``"state_bf16"`` rounds the recurrent state to
bfloat16 after every token; ``"bf16"`` rounds both operands of every
product outside the recurrence to bfloat16 (the dtype the configuration
states: what the program's weights and activations are kept in), and
``"bf16_all"`` the operands of the recurrence's products as well, the
state itself staying float32 (what one bfloat16 pass of a matrix unit
does to float32 operands).  ``None`` is the reference itself.  Every
rounding is a ``lax.reduce_precision``, which a compiler may not take out
as it may a pair of converts."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6


def head_dim(m):
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def layer_kinds(m) -> tuple:
    return tuple(m["layer_types"][:m["num_hidden_layers"]])


# ------------------------------------------------------------ lower precision

BF16 = ("bf16", "bf16_all")


def bf16(x):
    """``x`` at bfloat16's 8 bits of exponent and 7 of mantissa, still
    float32."""
    return jax.lax.reduce_precision(x, 8, 7)


def _round(x, axis, lower):
    """``x`` as the control ``lower`` multiplies it: bfloat16, or fp8 with
    one scale along ``axis``."""
    if lower in BF16:
        return bf16(x)
    if lower != "fp8":
        return x
    top = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    scale = top / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(x, w, lower=None):
    """x [..., k] @ w [k, n] in float32; under ``fp8`` both operands are
    rounded first (x per row, w per output column)."""
    return jnp.matmul(_round(x, -1, lower), _round(w, 0, lower),
                      precision="highest")


# ------------------------------------------------------------------ the model

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def mlp_residual(m, lp, x, lower):
    y = mm(jax.nn.silu(mm(x, lp["w_gate"], lower))
           * mm(x, lp["w_up"], lower), lp["w_down"], lower)
    return x + rms_norm(y, lp["mlp_norm"], m["rms_norm_eps"])


def delta_rule(q, k, v, alpha, beta, lower=None):
    """The recurrence over one sequence from an empty state, a token at a
    time.  q, k [s, H, dk], v [s, H, dv], alpha, beta [s, H] -> o
    [s, H, dv]."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    at = bf16 if lower == "bf16_all" else (lambda x: x)

    def one(S, row):
        q, k, v, a, b = row
        S = a[:, None, None] * S                              # [H, dv, dk]
        Sk = jnp.einsum("hvk,hk->hv", at(S), at(k), precision="highest")
        S = S + (b[:, None] * (v - Sk))[:, :, None] * k[:, None, :]
        if lower == "state_bf16":
            S = bf16(S)
        return S, jnp.einsum("hvk,hk->hv", at(S), at(q),
                             precision="highest")

    _, o = jax.lax.scan(one, jnp.zeros((H, dv, dk), F32),
                        (q, k, v, alpha, beta))
    return o


def linear_layer(m, lp, x, lower=None):
    """One linear-attention block on x [s, h]."""
    lp = {k: w.astype(F32) for k, w in lp.items()}
    s = x.shape[0]
    H, dk, dv, K = (m["linear_num_value_heads"], m["linear_key_head_dim"],
                    m["linear_value_head_dim"], m["linear_conv_kernel_dim"])
    qkv = jnp.concatenate([mm(x, lp[w], lower) for w in ("wq", "wk", "wv")],
                          axis=-1)
    xin = jnp.pad(qkv, ((K - 1, 0), (0, 0)))
    y = jax.nn.silu(sum(xin[i:i + s] * lp["conv_w"][i] for i in range(K)))
    q, k, v = jnp.split(y, [H * dk, 2 * H * dk], axis=-1)
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)
    q = unit(q.reshape(s, H, dk)) / np.sqrt(dk)
    k = unit(k.reshape(s, H, dk))
    v = v.reshape(s, H, dv)
    beta = jax.nn.sigmoid(mm(x, lp["wb"], lower))
    if m["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(lp["A_log"]) * jax.nn.softplus(
        mm(x, lp["wa"], lower) + lp["dt_bias"]))
    o = delta_rule(q, k, v, alpha, beta, lower)
    o = rms_norm(o, lp["o_norm"], m["rms_norm_eps"]).reshape(s, H * dv)
    y = mm(o * jax.nn.silu(mm(x, lp["wg"], lower)), lp["wo"], lower)
    x = x + rms_norm(y, lp["attn_norm"], m["rms_norm_eps"])
    return mlp_residual(m, lp, x, lower)


def attention(q, k, v, lower=None):
    """q [s, nh, hd], k/v [s, nkv, hd] -> [s, nh * hd]; causal; one group
    of query heads at a time so that the scores fit.  No rotary embedding:
    the published ``rope_theta`` is null (``hidden`` refuses another)."""
    at = bf16 if lower in BF16 else (lambda x: x)
    s, nh, hd = q.shape
    nkv = k.shape[1]
    qg = q.reshape(s, nkv, nh // nkv, hd).transpose(1, 2, 0, 3)
    keep = jnp.tril(jnp.ones((s, s), bool))

    def one(args):
        qq, kk, vv = args
        sc = jnp.einsum("gqd,kd->gqk", at(qq), at(kk),
                        precision="highest") / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->gqd", at(p), at(vv), precision="highest")

    out = jax.lax.map(one, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(2, 0, 1, 3).reshape(s, nh * hd)


def full_layer(m, lp, x, lower=None):
    """One full-attention block on x [s, h]."""
    lp = {k: w.astype(F32) for k, w in lp.items()}
    s = x.shape[0]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   head_dim(m))
    eps = m["rms_norm_eps"]
    q = rms_norm(mm(x, lp["wq"], lower), lp["q_norm"], eps)
    k = rms_norm(mm(x, lp["wk"], lower), lp["k_norm"], eps)
    v = mm(x, lp["wv"], lower).reshape(s, nkv, hd)
    o = attention(q.reshape(s, nh, hd), k.reshape(s, nkv, hd), v, lower)
    x = x + rms_norm(mm(o, lp["wo"], lower), lp["attn_norm"], eps)
    return mlp_residual(m, lp, x, lower)


LAYER = {LINEAR: ("linear", linear_layer), FULL: ("full", full_layer)}


def head(m, params, x, lower=None):
    w = params.get("lm_head")
    w = params["embed"].T if w is None else w
    xn = rms_norm(x, params["final_norm"].astype(F32), m["rms_norm_eps"])
    return mm(xn, w.astype(F32), lower)


@functools.partial(jax.jit, static_argnames=("m_key", "kind", "lower"))
def _layer_jit(m_key, kind, lp, x, lower):
    return LAYER[kind][1](dict(m_key), lp, x, lower)


@functools.partial(jax.jit, static_argnames=("m_key", "lower"))
def _head_jit(m_key, params_head, x, lower):
    return head(dict(m_key), params_head, x, lower)


def _key(m):
    """The configuration's scalars, hashable."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool, str))
                        or v is None))


def hidden(m, params, ids, lower=None):
    """ids [s] -> the last layer's rows [s, h], a layer at a time (each
    kind jitted once per length)."""
    if (m.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("rope_parameters.rope_theta is null in the "
                         "published configuration and no cell runs another "
                         "value: this reference has no rotary embedding")
    key = _key(m)
    x = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(F32)
    at = {LINEAR: 0, FULL: 0}
    for kind in layer_kinds(m):
        group = LAYER[kind][0]
        lp = {k: w[at[kind]] for k, w in params[group].items()}
        x = _layer_jit(key, kind, lp, x, lower)
        at[kind] += 1
    return x


def logits_at(m, params, ids, at=None, lower=None, pad_to=512):
    """float32 logits rows ``at`` (every row: None) of one sequence
    ``ids``; causal, so right padding changes nothing before it."""
    ids = np.asarray(ids, np.int32)
    n = -(-ids.size // pad_to) * pad_to
    padded = np.zeros(n, np.int32)
    padded[:ids.size] = ids
    at = np.arange(ids.size) if at is None else np.asarray(at)
    rows = jnp.take(hidden(m, params, padded, lower),
                    jnp.asarray(at.astype(np.int32)), axis=0)
    heads = {k: params[k] for k in ("lm_head", "embed", "final_norm")
             if k in params and (k != "embed" or "lm_head" not in params)}
    return _head_jit(_key(m), heads, rows, lower)


def control_gap(m, params, prompt, served, lower=None):
    """For one finished request: at each generated position, how far the
    served token's reference logit lies below the reference's best
    (``lower=None``), or how far the token that the control puts first lies
    below it; and the widest change the control made to any logit of those
    positions (0.0 for the reference itself: a control that reads 0.0
    there changed nothing and is no control).  Returns (the gaps, one a
    token; that change)."""
    prompt, served = np.asarray(prompt), np.asarray(served)
    ids = np.concatenate([prompt, served[:-1]])
    at = prompt.size - 1 + np.arange(served.size)
    ref = logits_at(m, params, ids, at)
    picked, moved = jnp.asarray(served), 0.0
    if lower is not None:
        low = logits_at(m, params, ids, at, lower)
        picked, moved = jnp.argmax(low, axis=-1), float(
            jnp.max(jnp.abs(low - ref)))
    mine = jnp.take_along_axis(ref, picked[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    return np.asarray(ref.max(axis=-1) - mine), moved


def served_gap(m, params, prompt, served, lower=None):
    """``control_gap``'s gaps, as ``runners/serve.check_outputs`` asks for
    them."""
    return control_gap(m, params, prompt, served, lower)[0]
