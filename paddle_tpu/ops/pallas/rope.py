"""Fused rotary position embedding (reference fused op:
python/paddle/incubate/nn/functional/fused_rotary_position_embedding.py,
fused_ops.yaml:428).

The rotate-half formulation used by Llama-family models.  This op is pure
elementwise-on-pairs — XLA fuses it perfectly into neighboring matmuls, so the
"kernel" is jnp (documented mapping per SURVEY.md §7: don't hand-write what XLA
already fuses); the Pallas escape hatch stays available for a fused
rope+attention prologue later."""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_inv_freq(inv_freq, rotary_dim, base, factor,
                  original_max_position_embeddings, beta_fast=32.0,
                  beta_slow=1.0):
    """YaRN's blended inverse frequencies: dimension ``i`` keeps its own
    frequency below the correction dimension of ``beta_fast`` rotations,
    takes ``1 / factor`` of it above that of ``beta_slow``, and is blended
    linearly between (Peng et al. 2023, the Hugging Face ``yarn``
    rope_type, ``truncate`` on)."""

    def correction_dim(rotations):
        return (rotary_dim * math.log(original_max_position_embeddings
                                      / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (inv_freq / factor) * ramp + inv_freq * (1.0 - ramp)


def yarn_attention_factor(factor):
    return 0.1 * math.log(factor) + 1.0


def rope_cos_sin(seq_len, head_dim, base=10000.0, position_ids=None,
                 dtype=jnp.float32, rotary_dim=None, yarn=None):
    """cos/sin tables [b_or_1, s, rotary_dim].  ``rotary_dim`` (default: the
    whole head) is how many leading dimensions of the head rotate;
    ``yarn`` (``factor``, ``original_max_position_embeddings`` and
    optionally ``beta_fast``, ``beta_slow``, ``attention_factor``) blends
    the frequencies and scales cos and sin by the attention factor."""
    r = head_dim if rotary_dim is None else int(rotary_dim)
    inv_freq = 1.0 / (base ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    scale = None
    if yarn is not None:
        inv_freq = yarn_inv_freq(
            inv_freq, r, base, yarn["factor"],
            yarn["original_max_position_embeddings"],
            yarn.get("beta_fast", 32.0), yarn.get("beta_slow", 1.0))
        scale = (yarn.get("attention_factor")
                 or yarn_attention_factor(yarn["factor"]))
    pos = (
        jnp.arange(seq_len, dtype=jnp.float32)[None, :]
        if position_ids is None
        else position_ids.astype(jnp.float32)
    )
    freqs = jnp.einsum("bs,d->bsd", pos, inv_freq)  # [b, s, r/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    if scale is None:
        return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)
    return ((jnp.cos(emb) * scale).astype(dtype),
            (jnp.sin(emb) * scale).astype(dtype))


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rotate(t, c, s):
    """Rotate the leading ``c.shape[-1]`` dimensions of the head, pass the
    rest through (partial rotary)."""
    r = c.shape[-1]
    if r == t.shape[-1]:
        return t * c + _rotate_half(t) * s
    tr = t[..., :r]
    tr = (tr * c + _rotate_half(tr) * s).astype(t.dtype)
    return jnp.concatenate([tr, t[..., r:]], axis=-1)


def apply_rotary_pos_emb(q, k, cos, sin):
    """q,k: [b, s, h, d]; cos,sin: [b_or_1, s, r] (r <= d: the leading r
    dimensions rotate) → broadcast over heads."""
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return _rotate(q, c, s).astype(q.dtype), _rotate(k, c, s).astype(k.dtype)


def fused_rotary_position_embedding(
    q, k=None, v=None, sin=None, cos=None, position_ids=None, use_neox_rotary_style=True
):
    """Paddle-compatible entry (v passes through untouched)."""
    b, s, h, d = q.shape
    if cos is None or sin is None:
        cos, sin = rope_cos_sin(s, d, position_ids=position_ids, dtype=q.dtype)
    else:
        cos = cos.reshape(cos.shape[0] if cos.ndim > 2 else 1, -1, d)
        sin = sin.reshape(sin.shape[0] if sin.ndim > 2 else 1, -1, d)
    outs = []
    c = cos[:, :, None, :]
    sn = sin[:, :, None, :]
    for t in (q, k, v):
        if t is None:
            outs.append(None)
        elif t is v:
            outs.append(t)
        else:
            outs.append((t * c + _rotate_half(t) * sn).astype(t.dtype))
    return tuple(outs)
