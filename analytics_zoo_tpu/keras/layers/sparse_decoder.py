"""A pre/post-norm decoder block with a per-layer kind: window or full
causal attention over grouped KV heads with QK-norm and a sigmoid
output gate, then a dense SwiGLU or a routed expert layer
(``moe.DroplessExperts``), four RMSNorms a layer:

    a = RMSNorm(h);  q, k, v, g = a Wq, a Wk, a Wv, a Wg   (no biases)
    q, k = RMSNorm_q(q), RMSNorm_k(k)          over each head's dim
    sliding layers: q, k = RoPE(q, k); full layers carry no positions
    o = attention(q, k, v; causal, window on sliding layers)
    h = h + RMSNorm(o * sigmoid(g)  Wo)
    h = h + RMSNorm(mlp(RMSNorm(h)))

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
from analytics_zoo_tpu.ops.attention import dot_product_attention

__all__ = ["RMSNorm", "rope", "kernel_ready", "GatedGroupedAttention",
           "SparseDecoderLayer", "SLIDING", "FULL"]

SLIDING, FULL = "sliding_attention", "full_attention"
# Values of an attention branch, named for ``jax.checkpoint`` policies
# (``save_only_these_names``; the decoders' ``nn.remat`` in
# ``models/text/sparse_decoder_lm.py`` keeps them): q, k and v as the
# attention call reads them, after the norms, RoPE and the heads-first
# transpose (``kernel_ready``; the three decoders' attention modules),
# and the output projection's result. Of this module's own: the query
# projection's result, which QK-norm's backward reads, and the output
# gate's pre-activation. Outside such a policy a name is the identity.
ATTENTION_Q_NAME = "attention_q"
ATTENTION_K_NAME = "attention_k"
ATTENTION_V_NAME = "attention_v"
ATTENTION_OUT_NAME = "attention_out"
ATTENTION_Q_PROJ_NAME = "attention_q_proj"
ATTENTION_GATE_NAME = "attention_gate"
# The MLP branch's result (the dense SwiGLU's or the expert layer's)
# before the norm that closes the branch, [B, L, d] whatever the
# experts' held load. That norm's backward reads it, so left to be
# computed again it brings back ``w2``, the shared expert's ``w2`` and
# the expert layer's combine. It is named here, outside the ``moe_*``
# scopes, under which nothing is kept.
MLP_OUT_NAME = "mlp_out"
# Initial scale of the two norms that close a residual branch. At 1, a
# freshly initialised stack adds to every position the same unit-RMS
# vector per attention branch (near-uniform attention over thousands of
# keys averages the values), the positions' states become collinear and
# every token picks the same experts (measured on the chip, PERF.md
# section 6, PR 27). Small, the token's own embedding leads until
# training says otherwise, as in a trained model.
BRANCH_SCALE_INIT = 0.1


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis,
    statistics in float32. With ``unit_offset`` the parameter is the
    scale's distance from 1 (``y * (1 + w)``); either way the scale
    starts at ``scale_init``."""

    eps: float = 1e-5
    dtype: Any = jnp.float32
    scale_init: float = 1.0
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.constant(
            self.scale_init - self.unit_offset), (x.shape[-1],))
        if self.unit_offset:
            scale = 1.0 + scale
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), -1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def rope(x, theta: float):
    """Rotary positions 0..L-1 on [B, L, H, D], rotate-half layout
    (the two halves of D pair up), computed in float32."""
    l, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(l, dtype=jnp.float32)[:, None] * inv_freq  # [L, D/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[None, :, None]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    return (x32 * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)


def kernel_ready(q, k, v):
    """q, k, v [B, H, L, D] as they enter the attention call, under
    their names."""
    return (checkpoint_name(q, ATTENTION_Q_NAME),
            checkpoint_name(k, ATTENTION_K_NAME),
            checkpoint_name(v, ATTENTION_V_NAME))


class GatedGroupedAttention(nn.Module):
    """Causal self-attention, ``n_head`` query heads over ``n_kv_head``
    KV heads, QK-norm, RoPE when ``window`` is set (the sliding kind),
    and ``sigmoid(x Wg)`` on the heads' output before ``Wo``."""

    n_head: int
    n_kv_head: int
    head_dim: int
    window: Optional[int] = None
    rope_theta: float = 10000.0
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, l, d = x.shape
        h, h_kv, hd = self.n_head, self.n_kv_head, self.head_dim

        def proj(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype,
                            name=name)(x)

        q = checkpoint_name(proj(h * hd, "q"),
                            ATTENTION_Q_PROJ_NAME).reshape(b, l, h, hd)
        k = proj(h_kv * hd, "k").reshape(b, l, h_kv, hd)
        v = proj(h_kv * hd, "v").reshape(b, l, h_kv, hd)
        gate = checkpoint_name(proj(h * hd, "gate"), ATTENTION_GATE_NAME)
        q = RMSNorm(self.eps, self.dtype, name="q_norm")(q)
        k = RMSNorm(self.eps, self.dtype, name="k_norm")(k)
        if self.window is not None:
            q, k = rope(q, self.rope_theta), rope(k, self.rope_theta)
        q, k, v = kernel_ready(q.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3))
        o = dot_product_attention(q, k, v, causal=True, window=self.window)
        o = o.transpose(0, 2, 1, 3).reshape(b, l, h * hd)
        return checkpoint_name(
            nn.Dense(d, use_bias=False, dtype=self.dtype, name="out")(
                o * jax.nn.sigmoid(gate)), ATTENTION_OUT_NAME)


class SparseDecoderLayer(nn.Module):
    """One block of the module docstring. ``kind`` is ``SLIDING`` or
    ``FULL``; ``experts`` holds ``DroplessExperts``' arguments, or is
    None for a dense SwiGLU of ``dense_width``."""

    kind: str
    n_head: int
    n_kv_head: int
    head_dim: int
    window: int
    dense_width: int
    experts: Optional[dict] = None
    rope_theta: float = 10000.0
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, train: bool = False):
        if self.kind not in (SLIDING, FULL):
            raise ValueError(f"unknown layer kind {self.kind!r}")

        def norm(name, scale_init=1.0):
            return RMSNorm(self.eps, self.dtype, scale_init, name=name)

        attn = GatedGroupedAttention(
            self.n_head, self.n_kv_head, self.head_dim,
            window=self.window if self.kind == SLIDING else None,
            rope_theta=self.rope_theta, eps=self.eps, dtype=self.dtype,
            name="attention")(norm("input_norm")(h))
        h = h + norm("post_attention_norm", BRANCH_SCALE_INIT)(attn)
        m = norm("pre_mlp_norm")(h)
        if self.experts is None:
            f = SwiGLU(self.dense_width, dtype=self.dtype, name="mlp")(m)
        else:
            f = DroplessExperts(**self.experts, dtype=self.dtype,
                                name="moe")(m, train=train)
        return h + norm("post_mlp_norm", BRANCH_SCALE_INIT)(
            checkpoint_name(f, MLP_OUT_NAME))
