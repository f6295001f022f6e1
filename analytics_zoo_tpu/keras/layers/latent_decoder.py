"""A pre-norm decoder block with latent attention (MLA): keys and
values come up from one low-rank latent a position, the rotary part of
the key is one head that every query head reads, and the values are
narrower than the queries. Two RMSNorms a layer, no QK-norm, no gate:

    a            = RMSNorm(h)
    q            = a Wq                   -> [L, H, nope + rot]
    c | k_r      = a Wkva                 -> [L, latent] | [L, rot]
    k_nope | v   = RMSNorm(c) Wkvb        -> [L, H, nope] | [L, H, v]
    q_rot, k_rot = RoPE(q[..., nope:]), RoPE(k_r)     k_rot: one head
    s_ij = (q_nope_i . k_nope_j + q_rot_i . k_rot_j) / sqrt(nope + rot)
    h = h + (softmax_{j <= i}(s) v) Wo
    h = h + mlp(RMSNorm(h))       dense SwiGLU, or ``DroplessExperts``

This is the training form: keys and values are expanded for every
position and the owned flash kernel reads them at their two widths
(``ops.attention.dot_product_attention``, scope
``attention_<path>_latent``). The latent cache and the absorbed decode
path are not here (ROADMAP M3).

Parameters are float32; ``dtype`` is the matmuls' and activations'
type. Norm statistics, the softmax and the router stay float32.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.keras.layers.moe import DroplessExperts, SwiGLU
from analytics_zoo_tpu.keras.layers.sparse_decoder import (
    ATTENTION_OUT_NAME, RMSNorm, kernel_ready, rope)
from analytics_zoo_tpu.ops.attention import dot_product_attention

__all__ = ["LatentAttention", "LatentDecoderLayer"]

# The one rotary key head every query head reads, as the attention call
# reads it: kept with ``sparse_decoder.kernel_ready``'s three by a
# caller that rematerialises the layer.
ATTENTION_K_ROT_NAME = "attention_k_rot"


class LatentAttention(nn.Module):
    """Causal self-attention through a ``latent_dim``-wide latent:
    ``n_head`` heads whose queries and keys are ``nope_dim + rope_dim``
    wide (RoPE on the last ``rope_dim`` only, the key's rotary part
    shared by the heads) and whose values are ``v_dim`` wide."""

    n_head: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    latent_dim: int
    rope_theta: float = 10000.0
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, l, d = x.shape
        h, nope, rot = self.n_head, self.nope_dim, self.rope_dim

        def proj(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype, name=name)

        q = proj(h * (nope + rot), "q")(x).reshape(b, l, h, nope + rot)
        down = proj(self.latent_dim + rot, "kv_down")(x)
        latent = RMSNorm(self.eps, self.dtype, name="latent_norm")(
            down[..., :self.latent_dim])
        kv = proj(h * (nope + self.v_dim), "kv_up")(latent).reshape(
            b, l, h, nope + self.v_dim)
        with jax.named_scope("latent_rope"):
            # heads first; the rotary key stays one head
            q = jnp.concatenate(
                [q[..., :nope], rope(q[..., nope:], self.rope_theta)],
                axis=-1).transpose(0, 2, 1, 3)
            k_rot = checkpoint_name(
                rope(down[:, :, None, self.latent_dim:],
                     self.rope_theta).transpose(0, 2, 1, 3),
                ATTENTION_K_ROT_NAME)
            q, k_nope, v = kernel_ready(
                q, kv[..., :nope].transpose(0, 2, 1, 3),
                kv[..., nope:].transpose(0, 2, 1, 3))
        o = dot_product_attention(q, k_nope, v, causal=True,
                                  k_shared=k_rot)
        o = o.transpose(0, 2, 1, 3).reshape(b, l, h * self.v_dim)
        return checkpoint_name(proj(d, "out")(o), ATTENTION_OUT_NAME)


class LatentDecoderLayer(nn.Module):
    """One block of the module docstring. ``attention`` holds
    ``LatentAttention``'s arguments; ``experts`` holds
    ``DroplessExperts``', or is None for a dense SwiGLU of
    ``dense_width``."""

    attention: dict
    dense_width: int
    experts: Optional[dict] = None
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, train: bool = False):
        def norm(name):
            return RMSNorm(self.eps, self.dtype, name=name)

        h = h + LatentAttention(**self.attention, eps=self.eps,
                                dtype=self.dtype, name="attention")(
            norm("input_norm")(h))
        m = norm("pre_mlp_norm")(h)
        if self.experts is None:
            return h + SwiGLU(self.dense_width, dtype=self.dtype,
                              name="mlp")(m)
        return h + DroplessExperts(**self.experts, dtype=self.dtype,
                                   name="moe")(m, train=train)
