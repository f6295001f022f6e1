"""Core layers (ref: zoo/.../keras/layers/{Dense,Dropout,Flatten,Reshape,
Permute,RepeatVector,Highway,SReLU,GaussianNoise,...}.scala)."""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras import activations
from analytics_zoo_tpu.keras.layers.base import FnModule, KerasLayer
from analytics_zoo_tpu.ops import dropout as ops_dropout


class _DenseModule(nn.Module):
    units: int
    activation: Callable
    use_bias: bool

    @nn.compact
    def __call__(self, x, train: bool = False):
        y = nn.Dense(self.units, use_bias=self.use_bias)(x)
        return self.activation(y)


class Dense(KerasLayer):
    """(ref: keras/layers/Dense.scala)."""

    def __init__(self, output_dim: int, activation=None, bias: bool = True,
                 **kwargs):
        super().__init__(**kwargs)
        self.output_dim = output_dim
        self.activation = activations.get(activation)
        self.bias = bias

    def _make_module(self):
        return _DenseModule(units=self.output_dim,
                            activation=self.activation, use_bias=self.bias)


class Activation(KerasLayer):
    def __init__(self, activation, **kwargs):
        super().__init__(**kwargs)
        self.activation = activations.get(activation)

    def _make_module(self):
        return FnModule(fn=self.activation)


class _DropoutModule(nn.Module):
    rate: float

    @nn.compact
    def __call__(self, x, train: bool = False):
        return ops_dropout.Dropout(self.rate, deterministic=not train)(x)


class Dropout(KerasLayer):
    """(ref: keras/layers/Dropout.scala)."""

    def __init__(self, p: float, **kwargs):
        super().__init__(**kwargs)
        self.p = p

    def _make_module(self):
        return _DropoutModule(rate=self.p)


class _GaussianNoiseModule(nn.Module):
    sigma: float

    @nn.compact
    def __call__(self, x, train: bool = False):
        if not train:
            return x
        rng = self.make_rng("dropout")
        return x + self.sigma * jax.random.normal(rng, x.shape, x.dtype)


class GaussianNoise(KerasLayer):
    """(ref: keras/layers/GaussianNoise.scala)."""

    def __init__(self, sigma: float, **kwargs):
        super().__init__(**kwargs)
        self.sigma = sigma

    def _make_module(self):
        return _GaussianNoiseModule(sigma=self.sigma)


class Flatten(KerasLayer):
    def _make_module(self):
        return FnModule(fn=lambda x: x.reshape(x.shape[0], -1))


class Reshape(KerasLayer):
    """target_shape excludes the batch dim; one -1 allowed
    (ref: keras/layers/Reshape.scala)."""

    def __init__(self, target_shape: Sequence[int], **kwargs):
        super().__init__(**kwargs)
        self.target_shape = tuple(target_shape)

    def _make_module(self):
        ts = self.target_shape
        return FnModule(fn=lambda x: x.reshape((x.shape[0],) + ts))


class Permute(KerasLayer):
    """1-based dim indices excluding batch (keras1 convention,
    ref: keras/layers/Permute.scala)."""

    def __init__(self, dims: Sequence[int], **kwargs):
        super().__init__(**kwargs)
        self.dims = tuple(dims)

    def _make_module(self):
        perm = (0,) + tuple(d for d in self.dims)
        return FnModule(fn=lambda x: jnp.transpose(x, perm))


class RepeatVector(KerasLayer):
    """[B, D] -> [B, n, D] (ref: keras/layers/RepeatVector.scala)."""

    def __init__(self, n: int, **kwargs):
        super().__init__(**kwargs)
        self.n = n

    def _make_module(self):
        n = self.n
        return FnModule(fn=lambda x: jnp.repeat(x[:, None, :], n, axis=1))


class Lambda(KerasLayer):
    """Wrap an arbitrary jax-traceable function
    (ref: api/autograd Lambda.scala / CustomLoss pattern)."""

    def __init__(self, fn: Callable, **kwargs):
        super().__init__(**kwargs)
        self.fn = fn

    def _make_module(self):
        return FnModule(fn=self.fn)


class InputLayer(KerasLayer):
    def _make_module(self):
        return FnModule(fn=lambda x: x)


class _HighwayModule(nn.Module):
    activation: Callable

    @nn.compact
    def __call__(self, x, train: bool = False):
        d = x.shape[-1]
        h = self.activation(nn.Dense(d, name="transform")(x))
        t = jax.nn.sigmoid(nn.Dense(
            d, name="gate",
            bias_init=nn.initializers.constant(-2.0))(x))
        return h * t + x * (1.0 - t)


class Highway(KerasLayer):
    """(ref: keras/layers/Highway.scala; gate bias init -2 per paper)."""

    def __init__(self, activation="tanh", **kwargs):
        super().__init__(**kwargs)
        self.activation = activations.get(activation)

    def _make_module(self):
        return _HighwayModule(activation=self.activation)


class _SReLUModule(nn.Module):
    """S-shaped ReLU with learnable (t_left, a_left, t_right, a_right)
    per-channel (ref: keras/layers/SReLU.scala; Jin et al. 2015)."""

    @nn.compact
    def __call__(self, x, train: bool = False):
        shape = (x.shape[-1],)
        t_l = self.param("t_left", nn.initializers.zeros, shape)
        a_l = self.param("a_left", nn.initializers.constant(0.2), shape)
        t_r = self.param("t_right", nn.initializers.constant(1.0), shape)
        a_r = self.param("a_right", nn.initializers.ones, shape)
        below = t_l + a_l * (x - t_l)
        above = t_r + a_r * (x - t_r)
        mid = x
        return jnp.where(x < t_l, below, jnp.where(x > t_r, above, mid))


class SReLU(KerasLayer):
    def _make_module(self):
        return _SReLUModule()


class Masking(KerasLayer):
    """Zero out timesteps whose features ALL equal ``mask_value``
    (ref: keras/layers/Masking.scala -- BigDL likewise zeroes masked
    steps): [B, T, ...] -> same shape with masked steps zeroed.
    Sum/max pooling then ignores them; RNNs still run their recurrence
    over the zeroed steps (no mask channel propagates -- same as the
    reference's BigDL layer set)."""

    def __init__(self, mask_value: float = 0.0, **kwargs):
        super().__init__(**kwargs)
        self.mask_value = mask_value

    def _make_module(self):
        mv = self.mask_value

        def fn(x):
            reduce_axes = tuple(range(2, x.ndim))
            keep = jnp.any(x != mv, axis=reduce_axes) if reduce_axes \
                else (x != mv)
            shape = keep.shape + (1,) * (x.ndim - keep.ndim)
            return x * keep.reshape(shape).astype(x.dtype)

        return FnModule(fn=fn)


class _MaxoutDenseModule(nn.Module):
    units: int
    nb_feature: int
    use_bias: bool

    @nn.compact
    def __call__(self, x, train: bool = False):
        y = nn.Dense(self.units * self.nb_feature,
                     use_bias=self.use_bias)(x)
        y = y.reshape(y.shape[:-1] + (self.nb_feature, self.units))
        return jnp.max(y, axis=-2)


class MaxoutDense(KerasLayer):
    """Max over ``nb_feature`` linear pieces
    (ref: keras/layers/MaxoutDense.scala)."""

    def __init__(self, output_dim: int, nb_feature: int = 4,
                 bias: bool = True, **kwargs):
        super().__init__(**kwargs)
        self.output_dim = output_dim
        self.nb_feature = nb_feature
        self.bias = bias

    def _make_module(self):
        return _MaxoutDenseModule(units=self.output_dim,
                                  nb_feature=self.nb_feature,
                                  use_bias=self.bias)


class _GaussianDropoutModule(nn.Module):
    rate: float

    @nn.compact
    def __call__(self, x, train: bool = False):
        if not train or self.rate <= 0:
            return x
        rng = self.make_rng("dropout")
        stddev = (self.rate / (1.0 - self.rate)) ** 0.5
        return x * (1.0 + stddev * jax.random.normal(rng, x.shape,
                                                     x.dtype))


class GaussianDropout(KerasLayer):
    """Multiplicative 1-centered gaussian noise
    (ref: keras/layers/GaussianDropout.scala)."""

    def __init__(self, p: float, **kwargs):
        super().__init__(**kwargs)
        if not 0.0 <= p < 1.0:
            raise ValueError(f"p must be in [0, 1), got {p}")
        self.p = p

    def _make_module(self):
        return _GaussianDropoutModule(rate=self.p)


class _SpatialDropoutModule(nn.Module):
    rate: float
    spatial_ndim: int

    @nn.compact
    def __call__(self, x, train: bool = False):
        if not train or self.rate <= 0:
            return x
        # drop whole channels: mask [B, 1, ..., 1, C]
        rng = self.make_rng("dropout")
        shape = (x.shape[0],) + (1,) * self.spatial_ndim + (x.shape[-1],)
        keep = jax.random.bernoulli(rng, 1.0 - self.rate, shape)
        return x * keep.astype(x.dtype) / (1.0 - self.rate)


class _SpatialDropoutBase(KerasLayer):
    spatial_ndim = 1

    def __init__(self, p: float = 0.5, **kwargs):
        super().__init__(**kwargs)
        if not 0.0 <= p < 1.0:
            raise ValueError(f"p must be in [0, 1), got {p}")
        self.p = p

    def _make_module(self):
        return _SpatialDropoutModule(rate=self.p,
                                     spatial_ndim=self.spatial_ndim)


class SpatialDropout1D(_SpatialDropoutBase):
    """Channel-wise dropout on [B, T, C]
    (ref: keras/layers/SpatialDropout1D.scala; channels-last)."""

    spatial_ndim = 1


class SpatialDropout2D(_SpatialDropoutBase):
    """Channel-wise dropout on [B, H, W, C]
    (ref: keras/layers/SpatialDropout2D.scala)."""

    spatial_ndim = 2


class SpatialDropout3D(_SpatialDropoutBase):
    """Channel-wise dropout on [B, D, H, W, C]
    (ref: keras/layers/SpatialDropout3D.scala)."""

    spatial_ndim = 3


class _GaussianSamplerModule(nn.Module):
    @nn.compact
    def __call__(self, xs, train: bool = False):
        if not isinstance(xs, (list, tuple)) or len(xs) != 2:
            raise ValueError("GaussianSampler expects [mean, log_var]")
        mean, log_var = xs
        if not train:
            return mean
        rng = self.make_rng("dropout")
        eps = jax.random.normal(rng, mean.shape, mean.dtype)
        return mean + jnp.exp(0.5 * log_var) * eps


class GaussianSampler(KerasLayer):
    """VAE reparameterization: sample N(mean, exp(log_var)) while
    training, mean at inference (ref: keras/layers/GaussianSampler.scala
    -- the reference samples unconditionally; returning the mean at
    inference is the standard VAE deployment behavior)."""

    def _make_module(self):
        return _GaussianSamplerModule()
