"""Normalization layers (ref: zoo/.../keras/layers/BatchNormalization.scala,
zoo/.../keras/layers/internal LayerNorm used by Transformer/BERT)."""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax.numpy as jnp

from analytics_zoo_tpu.keras.layers.base import FnModule, KerasLayer


def batch_norm(train: bool, dtype, momentum: float = 0.9,
               epsilon: float = 1e-3):
    """The backbones' BN factory: the one home of their momentum and
    epsilon (flax ``nn.BatchNorm``, exact full-batch statistics)."""
    return partial(nn.BatchNorm, use_running_average=not train,
                   momentum=momentum, epsilon=epsilon, dtype=dtype)


class _BatchNormModule(nn.Module):
    momentum: float
    epsilon: float

    @nn.compact
    def __call__(self, x, train: bool = False):
        return nn.BatchNorm(use_running_average=not train,
                            momentum=self.momentum,
                            epsilon=self.epsilon)(x)


class BatchNormalization(KerasLayer):
    """(ref: keras/layers/BatchNormalization.scala; running stats live in
    the ``batch_stats`` collection the Estimator threads through)."""

    def __init__(self, momentum: float = 0.99, epsilon: float = 1e-3,
                 **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.epsilon = epsilon

    def _make_module(self):
        return _BatchNormModule(momentum=self.momentum,
                                epsilon=self.epsilon)


class _LayerNormModule(nn.Module):
    epsilon: float

    @nn.compact
    def __call__(self, x, train: bool = False):
        return nn.LayerNorm(epsilon=self.epsilon)(x)


class LayerNormalization(KerasLayer):
    """(ref: TransformerLayer.scala's internal LayerNorm)."""

    def __init__(self, epsilon: float = 1e-5, **kwargs):
        super().__init__(**kwargs)
        self.epsilon = epsilon

    def _make_module(self):
        return _LayerNormModule(epsilon=self.epsilon)


class LRN2D(KerasLayer):
    """Local response normalization across channels on [B, H, W, C]
    (ref: keras/layers/LRN2D.scala):
    ``x / (k + alpha/n * sum_{local n channels} x^2)^beta``."""

    def __init__(self, alpha: float = 1e-4, k: float = 1.0, beta: float =
                 0.75, n: int = 5, **kwargs):
        super().__init__(**kwargs)
        self.alpha, self.k, self.beta, self.n = alpha, k, beta, n

    def _make_module(self):
        alpha, k, beta, n = self.alpha, self.k, self.beta, self.n

        def fn(x):
            sq = x * x
            half = n // 2
            pad = [(0, 0)] * (x.ndim - 1) + [(half, half)]
            padded = jnp.pad(sq, pad)
            acc = jnp.zeros_like(x)
            for i in range(n):
                acc = acc + padded[..., i:i + x.shape[-1]]
            return x / jnp.power(k + (alpha / n) * acc, beta)

        return FnModule(fn=fn)
