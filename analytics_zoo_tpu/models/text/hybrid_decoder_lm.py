"""A causal language model of hybrid decoder blocks (Qwen3-Next), trained
on the next token:

    h0 = Embed[ids]
    h  = layers(h0)       layer i: gated full attention where
                          (i + 1) % full_attention_interval == 0,
                          Gated DeltaNet elsewhere; routed experts in all
    logits = RMSNorm(h) W_head     (zero-centred norm, untied, float32)

``keras/layers/hybrid_decoder.py`` has the block. The experts are
softmax-routed with renormalised top-k, beside a shared expert behind a
sigmoid gate, and each layer sows its Switch balance loss
(``aux_loss_coef`` over the layers: their mean), which the Estimator
adds to the next-token loss. The vocabulary and the experts may be one
chip's share of a larger deployment, as in ``sparse_decoder_lm``. Each
layer is rematerialised for the backward pass and keeps
``sparse_decoder_lm.KEPT_NAMES``: on an attention layer the flash
kernel's two results, q, k, v, the query product, the gate and the
output projection, on every layer the shared expert's pre-activations;
the Gated DeltaNet's chunk recurrence runs again in the second
forward, which writes the states its backward kernel reads.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp

from analytics_zoo_tpu.keras.layers.hybrid_decoder import (
    FULL, LINEAR, HybridDecoderLayer)
from analytics_zoo_tpu.models.common import register_model
from analytics_zoo_tpu.models.text.sparse_decoder_lm import (
    _DecoderLM, _embed, _logits, _rematerialised)

__all__ = ["HybridDecoderLM", "HybridDecoderModule", "layer_kinds"]


def layer_kinds(n_layers: int, full_attention_interval: int) -> tuple:
    """Layer i is full attention where ``(i + 1) %
    full_attention_interval == 0``, Gated DeltaNet elsewhere."""
    return tuple(FULL if (i + 1) % full_attention_interval == 0 else LINEAR
                 for i in range(n_layers))


class HybridDecoderModule(nn.Module):
    vocab: int
    hidden_size: int
    n_layers: int
    full_attention_interval: int
    n_head: int
    n_kv_head: int
    head_dim: int
    partial_rotary_factor: float
    n_k_head: int
    n_v_head: int
    head_k_dim: int
    head_v_dim: int
    expert_width: int
    n_routed: int
    n_held: int
    first_held: int = 0
    top_k: int = 10
    shared_width: int = 0
    aux_loss_coef: float = 0.0
    conv_width: int = 4
    chunk: int = 64
    rope_theta: float = 10000.0
    eps: float = 1e-6
    scale_embedding: bool = False
    embed_init_std: float = 0.02
    router_init_std: Optional[float] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = _embed(self, x, self.embed_init_std)
        linear = dict(n_k_head=self.n_k_head, n_v_head=self.n_v_head,
                      head_k_dim=self.head_k_dim,
                      head_v_dim=self.head_v_dim,
                      conv_width=self.conv_width, chunk=self.chunk)
        attention = dict(n_head=self.n_head, n_kv_head=self.n_kv_head,
                         head_dim=self.head_dim,
                         rope_dims=int(self.head_dim
                                       * self.partial_rotary_factor),
                         rope_theta=self.rope_theta)
        experts = dict(
            width=self.expert_width, n_routed=self.n_routed,
            n_held=self.n_held, first_held=self.first_held,
            top_k=self.top_k, shared_width=self.shared_width,
            bias_step=0.0, router_init_std=self.router_init_std,
            score="softmax", shared_gate=True,
            aux_loss_coef=self.aux_loss_coef / self.n_layers)
        layer = _rematerialised(HybridDecoderLayer)
        kinds = layer_kinds(self.n_layers, self.full_attention_interval)
        for i, kind in enumerate(kinds):
            h = layer(kind=kind, linear=linear, attention=attention,
                      experts=experts, eps=self.eps, dtype=self.dtype,
                      name=f"layer_{i}")(h, train)
        return _logits(self, h, unit_offset=True)


@register_model
class HybridDecoderLM(_DecoderLM):
    """A decoder of ``HybridDecoderLayer`` blocks, three Gated DeltaNet
    layers to one of gated attention at ``full_attention_interval`` 4
    (RoPE on the leading ``partial_rotary_factor`` of each head);
    ``_DecoderLM``'s contract (the balance loss rides the ``losses``
    collection). ``embed_init_std`` / ``router_init_std``: how a fresh
    model starts (the benchmark configuration's
    ``assumed.initialisation`` says why)."""

    def __init__(self, vocab: int, hidden_size: int, n_layers: int,
                 full_attention_interval: int, n_head: int, n_kv_head: int,
                 head_dim: int, partial_rotary_factor: float,
                 n_k_head: int, n_v_head: int, head_k_dim: int,
                 head_v_dim: int,
                 expert_width: int, n_routed: int, n_held: int,
                 first_held: int = 0, top_k: int = 10,
                 shared_width: int = 0, aux_loss_coef: float = 0.0,
                 conv_width: int = 4, chunk: int = 64,
                 rope_theta: float = 10000.0, eps: float = 1e-6,
                 embed_init_std: float = 0.02,
                 router_init_std: Optional[float] = None,
                 dtype: str = "float32"):
        super().__init__(
            vocab=vocab, hidden_size=hidden_size, n_layers=n_layers,
            full_attention_interval=full_attention_interval,
            n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
            partial_rotary_factor=partial_rotary_factor,
            n_k_head=n_k_head, n_v_head=n_v_head,
            head_k_dim=head_k_dim, head_v_dim=head_v_dim,
            expert_width=expert_width, n_routed=n_routed, n_held=n_held,
            first_held=first_held, top_k=top_k, shared_width=shared_width,
            aux_loss_coef=aux_loss_coef, conv_width=conv_width,
            chunk=chunk, rope_theta=rope_theta, eps=eps,
            embed_init_std=embed_init_std,
            router_init_std=router_init_std, dtype=dtype)

    def _build_module(self):
        c = dict(self._config)
        c["dtype"] = jnp.dtype(c["dtype"])
        return HybridDecoderModule(**c)
