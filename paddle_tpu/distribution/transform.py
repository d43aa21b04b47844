"""Bijective transforms (reference: python/paddle/distribution/transform.py
— Transform hierarchy with forward/inverse/log_det_jacobian, consumed by
TransformedDistribution)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, _unwrap

__all__ = [
    "Transform", "AffineTransform", "ExpTransform", "PowerTransform",
    "AbsTransform", "SigmoidTransform", "TanhTransform", "SoftmaxTransform",
    "ChainTransform", "IndependentTransform", "ReshapeTransform",
    "StackTransform", "StickBreakingTransform",
]


class Transform:
    """Base (transform.py Transform): y = forward(x); log_det is d y / d x."""

    _domain_event_dim = 0

    def forward(self, x):
        return Tensor(self._forward(_unwrap(x)))

    def inverse(self, y):
        return Tensor(self._inverse(_unwrap(y)))

    def forward_log_det_jacobian(self, x):
        return Tensor(self._fldj(_unwrap(x)))

    def inverse_log_det_jacobian(self, y):
        return Tensor(-self._fldj(self._inverse(_unwrap(y))))

    def _forward(self, x):
        raise NotImplementedError

    def _inverse(self, y):
        raise NotImplementedError

    def _fldj(self, x):
        raise NotImplementedError

    def __call__(self, x):
        return self.forward(x)


class AffineTransform(Transform):
    def __init__(self, loc, scale):
        self.loc = jnp.asarray(_unwrap(loc))
        self.scale = jnp.asarray(_unwrap(scale))

    def _forward(self, x):
        return self.loc + self.scale * x

    def _inverse(self, y):
        return (y - self.loc) / self.scale

    def _fldj(self, x):
        return jnp.broadcast_to(jnp.log(jnp.abs(self.scale)), x.shape)


class ExpTransform(Transform):
    def _forward(self, x):
        return jnp.exp(x)

    def _inverse(self, y):
        return jnp.log(y)

    def _fldj(self, x):
        return x


class PowerTransform(Transform):
    def __init__(self, power):
        self.power = jnp.asarray(_unwrap(power))

    def _forward(self, x):
        return jnp.power(x, self.power)

    def _inverse(self, y):
        return jnp.power(y, 1.0 / self.power)

    def _fldj(self, x):
        return jnp.log(jnp.abs(self.power * jnp.power(x, self.power - 1)))


class AbsTransform(Transform):
    """Non-injective |x| (transform.py AbsTransform); inverse returns the
    positive branch."""

    def _forward(self, x):
        return jnp.abs(x)

    def _inverse(self, y):
        return y

    def _fldj(self, x):
        raise NotImplementedError("AbsTransform is not bijective")


class SigmoidTransform(Transform):
    def _forward(self, x):
        return jax.nn.sigmoid(x)

    def _inverse(self, y):
        return jnp.log(y) - jnp.log1p(-y)

    def _fldj(self, x):
        return -jax.nn.softplus(-x) - jax.nn.softplus(x)


class TanhTransform(Transform):
    def _forward(self, x):
        return jnp.tanh(x)

    def _inverse(self, y):
        return jnp.arctanh(y)

    def _fldj(self, x):
        # log(1 - tanh^2 x) = 2(log 2 - x - softplus(-2x))
        return 2.0 * (jnp.log(2.0) - x - jax.nn.softplus(-2.0 * x))


class SoftmaxTransform(Transform):
    _domain_event_dim = 1

    def _forward(self, x):
        return jax.nn.softmax(x, axis=-1)

    def _inverse(self, y):
        return jnp.log(y)

    def _fldj(self, x):
        raise NotImplementedError("softmax is not bijective")


class StickBreakingTransform(Transform):
    """R^{K} → simplex^{K+1} (transform.py StickBreakingTransform)."""

    _domain_event_dim = 1

    def _forward(self, x):
        offset = x.shape[-1] - jnp.cumsum(jnp.ones_like(x), axis=-1) + 1
        z = jax.nn.sigmoid(x - jnp.log(offset))
        zpad = jnp.concatenate([z, jnp.ones_like(z[..., :1])], axis=-1)
        onez = jnp.concatenate([jnp.ones_like(z[..., :1]), 1 - z], axis=-1)
        return zpad * jnp.cumprod(onez, axis=-1)

    def _inverse(self, y):
        ycum = jnp.cumsum(y[..., :-1], axis=-1)
        offset = y.shape[-1] - 1 - jnp.cumsum(jnp.ones_like(y[..., :-1]),
                                              axis=-1) + 1
        z = y[..., :-1] / (1 - jnp.concatenate(
            [jnp.zeros_like(ycum[..., :1]), ycum[..., :-1]], axis=-1))
        return jnp.log(z) - jnp.log1p(-z) + jnp.log(offset)

    def _fldj(self, x):
        # standard identity (1 - sigmoid(t) = exp(-t)·sigmoid(t)):
        # log|det J| = Σ_k [-t_k + logsigmoid(t_k) + log y_k],
        # t = x - log(offset), y = forward(x) head
        offset = x.shape[-1] - jnp.cumsum(jnp.ones_like(x), axis=-1) + 1
        t = x - jnp.log(offset)
        y = self._forward(x)
        return jnp.sum(-t + jax.nn.log_sigmoid(t) + jnp.log(y[..., :-1]),
                       axis=-1)


class ChainTransform(Transform):
    def __init__(self, transforms):
        self.transforms = list(transforms)
        self._domain_event_dim = max(
            (t._domain_event_dim for t in self.transforms), default=0)

    def _forward(self, x):
        for t in self.transforms:
            x = t._forward(x)
        return x

    def _inverse(self, y):
        for t in reversed(self.transforms):
            y = t._inverse(y)
        return y

    def _fldj(self, x):
        # terms must agree on event rank before summing: a per-element
        # [..., K] term from a scalar transform is reduced over the chain's
        # event dims so it aligns with event-reduced [...] terms
        total = 0.0
        for t in self.transforms:
            ldj = t._fldj(x)
            extra = self._domain_event_dim - t._domain_event_dim
            if extra > 0 and jnp.ndim(ldj) >= extra:
                ldj = jnp.sum(ldj, axis=tuple(range(-extra, 0)))
            total = total + ldj
            x = t._forward(x)
        return total


class IndependentTransform(Transform):
    """Reinterpret trailing dims as event dims: log_det sums over them."""

    def __init__(self, base, reinterpreted_batch_rank):
        self.base = base
        self.rank = int(reinterpreted_batch_rank)
        self._domain_event_dim = base._domain_event_dim + self.rank

    def _forward(self, x):
        return self.base._forward(x)

    def _inverse(self, y):
        return self.base._inverse(y)

    def _fldj(self, x):
        ld = self.base._fldj(x)
        return jnp.sum(ld, axis=tuple(range(-self.rank, 0)))


class ReshapeTransform(Transform):
    def __init__(self, in_event_shape, out_event_shape):
        self.in_event_shape = tuple(in_event_shape)
        self.out_event_shape = tuple(out_event_shape)
        self._domain_event_dim = len(self.in_event_shape)

    def _forward(self, x):
        batch = x.shape[: x.ndim - len(self.in_event_shape)]
        return x.reshape(batch + self.out_event_shape)

    def _inverse(self, y):
        batch = y.shape[: y.ndim - len(self.out_event_shape)]
        return y.reshape(batch + self.in_event_shape)

    def _fldj(self, x):
        batch = x.shape[: x.ndim - len(self.in_event_shape)]
        return jnp.zeros(batch)


class StackTransform(Transform):
    """Apply one transform per slice along ``axis``."""

    def __init__(self, transforms, axis=0):
        self.transforms = list(transforms)
        self.axis = int(axis)

    def _map(self, x, method):
        parts = [getattr(t, method)(xi) for t, xi in
                 zip(self.transforms, jnp.moveaxis(x, self.axis, 0))]
        return jnp.stack(parts, axis=self.axis)

    def _forward(self, x):
        return self._map(x, "_forward")

    def _inverse(self, y):
        return self._map(y, "_inverse")

    def _fldj(self, x):
        return self._map(x, "_fldj")
