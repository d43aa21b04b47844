"""Fused RMSNorm (Pallas TPU kernel).

Reference fused op: python/paddle/incubate/nn/functional/fused_rms_norm.py
(CUDA kernel phi/kernels/fusion).  One pass over rows in VMEM: mean-of-squares,
rsqrt, scale — fp32 accumulation regardless of input dtype.
Backward via custom_vjp in closed form (XLA fuses it into a few kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.sharding import PartitionSpec as P

from . import interpret_mode, kernel_disabled, per_shard


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    o_ref[:] = (x * inv * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def rms_norm_ref(x, w, eps=1e-6):
    """The pure-jnp composition (the kernel's exact f32 math, no Pallas
    launch): the ``rms_norm`` kill-switch fallback, and the inline form
    the fused-layer decode path uses where a separate launch on [B, 1, h]
    activations is pure dispatch tax (inference.transformer_apply,
    docs/paged_attention.md "Megastep stage 2" — XLA fuses this into the
    neighboring matmuls)."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * inv * w.astype(jnp.float32)).astype(x.dtype)


def _rms_fwd_pallas(x2d, w, eps):
    if kernel_disabled("rms_norm"):
        return rms_norm_ref(x2d, w, eps)
    rows, d = x2d.shape
    br = min(rows, 256)
    # pad ragged row counts up to the block grid instead of collapsing to a
    # single [rows, d] block (which blows VMEM at e.g. [8·2048+1, 4096] fp32);
    # rows are independent, zero rows normalize to zero, pad sliced off below
    pad = (-rows) % br
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=((rows + pad) // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x2d.dtype),
        name="rms_norm_fwd",
        interpret=interpret_mode(),
    )(x2d, w)
    return out[:rows] if pad else out


def _rms_rows(x, weight, eps):
    shape = x.shape
    out = _rms_fwd_pallas(x.reshape(-1, shape[-1]), weight, eps)
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, weight, eps=1e-6):
    """x: [..., d], weight: [d]."""
    # on a mesh of several devices (ops.pallas.spmd_kernels) rows are
    # independent, so each device norms its own slice of the batch
    xs = lambda batch, _: P(batch, *([None] * (x.ndim - 1)))
    return per_shard(functools.partial(_rms_rows, eps=eps),
                     lambda b, h: ((xs(b, h), P()), xs(b, h)))(x, weight)


def _rms_vjp_fwd(x, weight, eps):
    return rms_norm(x, weight, eps), (x, weight)


def _rms_vjp_bwd(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    d = x.shape[-1]
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    xhat = xf * inv
    gw = gf * wf
    # d/dx [x * inv]: inv * (gw - xhat * mean(gw * xhat))
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dw.astype(w.dtype)


rms_norm.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)
