"""A dense sandwich-norm decoder block, the layer of a looped
(depth-recurrent, weight-shared) language model: plain multi-head
causal attention with RoPE on every dimension of every layer, a dense
SwiGLU, four RMSNorms a layer, no biases, no QK-norm and no output
gate:

    a = RMSNorm(u);  q, k, v = a Wq, a Wk, a Wv;  q, k = RoPE(q, k)
    u = u + RMSNorm( softmax_causal(q k^T / sqrt(head_dim)) v  Wo )
    m = RMSNorm(u)
    u = u + RMSNorm( (silu(m W1) * (m W3)) W2 )

The layer holds no state of its own, so a model may apply one instance
several times a step (``models/text/looped_decoder_lm.py`` applies the
whole stack ``n_passes`` times with one set of parameters).

Parameters are float32; ``dtype`` is the matmuls' and activations'
type. Norm statistics and the softmax stay float32.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.keras.layers.moe import SwiGLU
from analytics_zoo_tpu.keras.layers.sparse_decoder import (
    BRANCH_SCALE_INIT, MLP_OUT_NAME, RMSNorm, kernel_ready, rope)
from analytics_zoo_tpu.ops.attention import dot_product_attention

__all__ = ["RotaryAttention", "LoopedDecoderLayer"]


class RotaryAttention(nn.Module):
    """Causal self-attention of ``n_head`` heads of ``head_dim`` (as
    many KV heads), RoPE on all of q's and k's dimensions."""

    n_head: int
    head_dim: int
    rope_theta: float = 10000.0
    init_std: float = 0.02
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, l, d = x.shape
        h, hd = self.n_head, self.head_dim

        def proj(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype, name=name,
                            kernel_init=nn.initializers.normal(self.init_std))

        q, k, v = (proj(h * hd, name)(x).reshape(b, l, h, hd)
                   for name in ("q", "k", "v"))
        q, k, v = kernel_ready(
            rope(q, self.rope_theta).transpose(0, 2, 1, 3),
            rope(k, self.rope_theta).transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3))
        o = dot_product_attention(q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(b, l, h * hd)
        return proj(d, "out")(o)


class LoopedDecoderLayer(nn.Module):
    """One block of the module docstring. The two norms that close a
    branch start at ``BRANCH_SCALE_INIT`` (``sparse_decoder.py`` says
    why a sandwich block does not start them at 1)."""

    n_head: int
    head_dim: int
    dense_width: int
    rope_theta: float = 10000.0
    eps: float = 1e-6
    init_std: float = 0.02
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u, train: bool = False):
        def norm(name, scale_init=1.0):
            return RMSNorm(self.eps, self.dtype, scale_init, name=name)

        attn = RotaryAttention(
            self.n_head, self.head_dim, self.rope_theta, self.init_std,
            self.dtype, name="attention")(norm("input_norm")(u))
        u = u + norm("post_attention_norm", BRANCH_SCALE_INIT)(attn)
        f = SwiGLU(self.dense_width, dtype=self.dtype,
                   kernel_init=nn.initializers.normal(self.init_std),
                   name="mlp")(norm("pre_mlp_norm")(u))
        return u + norm("post_mlp_norm", BRANCH_SCALE_INIT)(
            checkpoint_name(f, MLP_OUT_NAME))
