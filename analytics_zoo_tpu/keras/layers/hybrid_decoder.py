"""A hybrid decoder block (Qwen3-Next): the token mixer is a Gated
DeltaNet linear-attention layer or gated full softmax attention, chosen
by the layer's kind; the channel mixer is a routed expert layer. Two
zero-centred RMSNorms a layer (``x_hat * (1 + w)``, statistics in
float32):

    h = h + mixer(RMSNorm(h))
    h = h + moe(RMSNorm(h))

Gated DeltaNet (``GatedDeltaNet``, module ``linear_mixer``):

    q | k | v | z = x W_qkvz      q, k: n_k heads; v, z: n_v heads
    b | a         = x W_ba        n_v each
    q | k | v     = SiLU(causal_conv(q | k | v))     depthwise, kernel 4
    q, k          = L2norm(q) / sqrt(d_k), L2norm(k)  per head; repeated
                                                     to n_v heads
    beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)
    o    = gated delta rule(q, k, v, g, beta)         ``ops.delta_rule``
    out  = (RMSNorm_dv(o) w * SiLU(z)) W_out

Gated attention is ``sparse_decoder.GatedGroupedAttention`` with partial
rotary and zero-centred QK-norms. The column layouts of ``W_qkvz`` and
``W_ba`` are contiguous ([q | k | v | z], [b | a]); the published
checkpoint groups them by key head, which with weights from a seed is a
fixed permutation of the columns.

Parameters are float32; ``dtype`` is the matmuls' and activations'
type. Norm statistics, the gates, the delta rule's state and the router
stay float32.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras.layers.moe import DroplessExperts
from analytics_zoo_tpu.keras.layers.sparse_decoder import (
    FULL, GatedGroupedAttention, RMSNorm)
from analytics_zoo_tpu.ops.delta_rule import (
    causal_short_conv, chunked_gated_delta_rule)

__all__ = ["GatedDeltaNet", "HybridDecoderLayer", "LINEAR", "FULL"]

LINEAR = "linear_attention"


def _l2_normalised(x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + eps)


def _conv_init(key, shape, dtype=jnp.float32):
    """PyTorch's ``Conv1d`` default for a depthwise kernel of ``K`` taps:
    uniform within +-1/sqrt(K)."""
    bound = 1.0 / shape[0] ** 0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log A`` with ``A`` uniform in (0, 16] (the published code)."""
    return jnp.log(16.0 - jax.random.uniform(key, shape, dtype, 0.0, 16.0))


class GatedDeltaNet(nn.Module):
    """The Gated DeltaNet token mixer of the module docstring:
    ``n_k_head`` query / key heads of ``head_k_dim``, ``n_v_head`` value
    heads of ``head_v_dim``, a causal convolution of ``conv_width`` taps
    over q, k and v, the delta rule ``chunk`` positions at a time.

    Collection ``counters``: ``delta_rule_chunks``, one per (row, value
    head, chunk) the delta rule ran over on a training apply."""

    n_k_head: int
    n_v_head: int
    head_k_dim: int
    head_v_dim: int
    conv_width: int = 4
    chunk: int = 64
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        b, l, d = x.shape
        h_k, h_v = self.n_k_head, self.n_v_head
        key_dim = h_k * self.head_k_dim
        value_dim = h_v * self.head_v_dim
        conv_dim = 2 * key_dim + value_dim

        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype, name=name)

        qkvz = dense(conv_dim + value_dim, "in_proj_qkvz")(x)
        ba = dense(2 * h_v, "in_proj_ba")(x)
        with jax.named_scope("short_conv"):
            conv = self.param("conv", _conv_init,
                              (self.conv_width, conv_dim))
            qkv = nn.silu(causal_short_conv(qkvz[..., :conv_dim], conv))
        z = qkvz[..., conv_dim:].reshape(b, l, h_v, self.head_v_dim)
        with jax.named_scope("delta_gates"):
            repeat = h_v // h_k

            def heads(x, n, dim):
                x = _l2_normalised(x.reshape(b, l, n, dim))
                return jnp.repeat(x, repeat, axis=2)

            q = heads(qkv[..., :key_dim], h_k, self.head_k_dim) * (
                self.head_k_dim ** -0.5)
            k = heads(qkv[..., key_dim:2 * key_dim], h_k, self.head_k_dim)
            v = qkv[..., 2 * key_dim:].reshape(b, l, h_v, self.head_v_dim)
            a_log = self.param("A_log", _a_log_init, (h_v,))
            dt_bias = self.param("dt_bias", nn.initializers.ones, (h_v,))
            ba = ba.astype(jnp.float32)
            beta = jax.nn.sigmoid(ba[..., :h_v])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., h_v:] + dt_bias)
        with jax.named_scope("delta_rule"):
            o = chunked_gated_delta_rule(
                q.astype(self.dtype), k.astype(self.dtype), v, g, beta,
                chunk=min(self.chunk, l))
        with jax.named_scope("gated_norm"):
            scale = self.param("norm", nn.initializers.ones,
                               (self.head_v_dim,))
            o32 = o.astype(jnp.float32)
            o = (o32 * jax.lax.rsqrt(
                jnp.mean(o32 * o32, -1, keepdims=True) + self.eps) * scale
                 * nn.silu(z.astype(jnp.float32))).astype(self.dtype)
        self._count(b * h_v * (l // min(self.chunk, l)), train)
        return dense(d, "out_proj")(o.reshape(b, l, value_dim))

    def _count(self, chunks: int, train: bool):
        counting = train and self.is_mutable_collection("counters")
        if not (counting or self.is_initializing()):
            return
        counter = self.variable("counters", "delta_rule_chunks",
                                lambda: jnp.zeros((), jnp.int32))
        if counting:
            counter.value = counter.value + chunks


class HybridDecoderLayer(nn.Module):
    """One block of the module docstring. ``kind`` is ``LINEAR`` (a
    ``GatedDeltaNet`` of ``linear``'s arguments) or ``FULL`` (a
    ``GatedGroupedAttention`` of ``attention``'s, with zero-centred
    QK-norms); ``experts`` holds ``DroplessExperts``' arguments."""

    kind: str
    linear: dict
    attention: dict
    experts: dict
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, train: bool = False):
        def norm(name):
            return RMSNorm(self.eps, self.dtype, unit_offset=True, name=name)

        a = norm("input_norm")(h)
        if self.kind == LINEAR:
            mixed = GatedDeltaNet(**self.linear, eps=self.eps,
                                  dtype=self.dtype, name="linear_mixer")(
                a, train)
        elif self.kind == FULL:
            mixed = GatedGroupedAttention(
                **self.attention, eps=self.eps, dtype=self.dtype,
                unit_offset=True, name="attention")(a)
        else:
            raise ValueError(f"unknown layer kind {self.kind!r}")
        h = h + mixed
        return h + DroplessExperts(**self.experts, dtype=self.dtype,
                                   name="moe")(norm("pre_mlp_norm")(h),
                                               train=train)
