"""A dense pre-norm decoder block with EVA attention ("Efficient
Attention via Control Variates", Zheng et al., ICLR 2023, in the
simplified form a byte-level model trains with): every chunk of
``chunk`` keys and values is pooled into one learned summary, and a
query reads the token keys of its own window exactly and the summaries
of every earlier window, all under one softmax. Per head, with learned
``phi``, ``mu`` and ``s = head_dim ** -0.5``:

    x        = RMSNorm1p(h)                 y * (1 + w); h is float32
    q, k, v  = x Wq, x Wk, x Wv             q, k <- RoPE on all dims
    pi_j     = softmax over chunk m's positions j of  s * (k_j . phi)
    k~_m     = sum_j pi_j k_j + mu          v~_m = sum_j pi_j v_j
    S_i = { j : window * w(i) <= j <= i },  w(i) = i // window
    R_i = { m : m < w(i) * window / chunk }
    o_i = softmax over S_i and R_i together of s * q_i . (k_j | k~_m),
          applied to (v_j | v~_m)
    h = h + o Wo                            added in float32
    h = h + SwiGLU(RMSNorm1p(h))            added in float32

A query never reads a summary of its own window and a summary is pooled
from 16 keys of an earlier one: nothing leaks from the future. The
joint softmax is ``ops.attention.eva_attention`` (scope
``attention_<path>_eva``); the pooling runs under the scope
``eva_chunk_summaries``.

Parameters are float32; ``dtype`` is the matmuls' and activations'
type; the residual stream, norm statistics, the pooling's and the
attention's softmax stay float32.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.keras.layers.moe import SwiGLU
from analytics_zoo_tpu.keras.layers.sparse_decoder import (
    ATTENTION_OUT_NAME, RMSNorm, kernel_ready, rope)
from analytics_zoo_tpu.obs.metrics import get_registry
from analytics_zoo_tpu.ops.attention import (
    dot_product_attention, eva_attention, eva_attention_path)
from analytics_zoo_tpu.ops.pallas_attention import eva_pairs

__all__ = ["EvaAttention", "ByteDecoderLayer", "chunk_summaries"]

# The MLP's input (the second norm's output), kept by a caller that
# rematerialises the layer: left to be computed again it has no
# consumer but the two weight-gradient products of SwiGLU, and XLA then
# folds the norm into their operands, which costs them a third of their
# speed at 11,008 columns (docs/kernels.md "Named results").
MLP_IN_NAME = "mlp_in"
# The chunk summaries as the attention call reads them (8 MB a layer
# for both at [1, 32, 512, 128]): kept, the second forward runs no
# pooling; its backward still computes the chunk softmax from the kept k.
EVA_K_SUMMARY_NAME = "eva_k_summary"
EVA_V_SUMMARY_NAME = "eva_v_summary"

_M_PAIRS = get_registry().gauge(
    "zoo_model_attention_eva_pairs_computed_ratio",
    "Score entries the EVA attention call's blocks compute, forward and "
    "backward, over the pairs its mask allows (set while the step is "
    "traced: a function of the shapes and the path)", ("module",))


def _clamped_normal(scale: float):
    """normal(0, 1) clamped to [-1, 1], times ``scale``."""
    def init(key, shape, dtype=jnp.float32):
        return jnp.clip(jax.random.normal(key, shape, dtype), -1, 1) * scale

    return init


def chunk_summaries(k, v, phi, mu, chunk: int, scale: float):
    """k, v [B, H, L, D], phi, mu [H, D] -> one summary a chunk of
    ``chunk`` positions, k~, v~ [B, H, L / chunk, D]: the chunk's keys
    and values under the softmax of ``scale * k . phi`` over its
    positions, ``mu`` added to the key. Computed in float32."""
    b, h, l, d = k.shape
    kc = k.astype(jnp.float32).reshape(b, h, l // chunk, chunk, d)
    vc = v.astype(jnp.float32).reshape(b, h, l // chunk, chunk, d)
    phi = phi.astype(jnp.float32)[None, :, None, None]
    pi = jax.nn.softmax(scale * jnp.sum(kc * phi, -1), axis=-1)[..., None]
    mu = mu.astype(jnp.float32)[None, :, None]
    return jnp.sum(pi * kc, axis=3) + mu, jnp.sum(pi * vc, axis=3)


class EvaAttention(nn.Module):
    """Causal self-attention of ``n_head`` heads of ``head_dim``, RoPE
    on every dimension, EVA's two key sources (module docstring)."""

    n_head: int
    head_dim: int
    window: int
    chunk: int
    rope_theta: float = 10000.0
    init_std: float = 0.02
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, l, d = x.shape
        h, hd = self.n_head, self.head_dim
        scale = hd ** -0.5

        def proj(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype, name=name,
                            kernel_init=nn.initializers.normal(self.init_std))

        q, k, v = (proj(h * hd, name)(x).reshape(b, l, h, hd)
                   for name in ("q", "k", "v"))
        q, k, v = kernel_ready(
            rope(q, self.rope_theta).transpose(0, 2, 1, 3),
            rope(k, self.rope_theta).transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3))
        phi = self.param("adaptive_phi", _clamped_normal(scale), (h, hd))
        mu = self.param("adaptive_mu_k", _clamped_normal(scale), (h, hd))
        if l <= self.window:
            # inside the first window there is no summary to read
            o = dot_product_attention(q, k, v, causal=True, scale=scale)
        else:
            with jax.named_scope("eva_chunk_summaries"):
                k_sum, v_sum = chunk_summaries(k, v, phi, mu, self.chunk,
                                               scale)
            self._publish_pairs(l, hd)
            o = eva_attention(
                q, k, v,
                checkpoint_name(k_sum.astype(self.dtype), EVA_K_SUMMARY_NAME),
                checkpoint_name(v_sum.astype(self.dtype), EVA_V_SUMMARY_NAME),
                self.window, scale)
        o = o.transpose(0, 2, 1, 3).reshape(b, l, h * hd)
        return checkpoint_name(proj(d, "out")(o), ATTENTION_OUT_NAME)

    def _publish_pairs(self, l: int, hd: int) -> None:
        """The gauge of the docstring above: what the kernels' blocks
        compute over what the mask allows; 1 where the scores are held
        whole and masked."""
        path = eva_attention_path(jax.default_backend(), l, self.window,
                                  self.chunk, hd, self.n_head)
        ratio = 1.0
        if path == "flash":
            pairs = eva_pairs(l, self.window, self.window // self.chunk, hd)
            ratio = ((pairs["computed_forward"] + pairs["computed_backward"])
                     / (2 * pairs["allowed"]))
        _M_PAIRS.labels(module="/".join(self.path)).set(ratio)


class ByteDecoderLayer(nn.Module):
    """One block of the module docstring: ``attention`` holds
    ``EvaAttention``'s arguments; the residual stream ``h`` is
    float32."""

    attention: dict
    dense_width: int
    eps: float = 1e-5
    init_std: float = 0.02
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, train: bool = False):
        def norm(name):
            return RMSNorm(self.eps, self.dtype, unit_offset=True, name=name)

        h = h.astype(jnp.float32)
        h = h + EvaAttention(
            **self.attention, init_std=self.init_std, dtype=self.dtype,
            name="attention")(norm("input_norm")(h)).astype(jnp.float32)
        return h + SwiGLU(
            self.dense_width, dtype=self.dtype,
            kernel_init=nn.initializers.normal(self.init_std),
            name="mlp")(checkpoint_name(norm("pre_mlp_norm")(h),
                                        MLP_IN_NAME)).astype(jnp.float32)
