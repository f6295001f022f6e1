"""Causal language models of sparse decoder blocks, trained on the
next token: leading dense layers and then routed experts.

    h0 = Embed[ids] * sqrt(d)     (``scale_embedding``)
    h  = layers(h0)
    logits = RMSNorm(h) W_head    (untied, float32)

``SparseDecoderLM`` stacks ``SparseDecoderLayer`` blocks (window and
full grouped-KV attention mixed by layer, four norms, an output gate),
``LatentDecoderLM`` stacks ``LatentDecoderLayer`` blocks (latent
attention, two norms); embedding, rematerialisation, final norm, head
and loss are shared. ``ByteDecoderLM`` is a dense decoder over bytes:
``ByteDecoderLayer`` blocks (EVA attention, two unit-offset norms, a
float32 residual stream) and ``n_pred_heads`` heads over the
vocabulary, head ``n`` at position ``t`` predicting byte ``t + 1 + n``
(``multi_byte_loss``); it shares the embedding, the rematerialisation
and the final norm and head's code.

The vocabulary and the experts may be one chip's share of a larger
deployment: ``vocab`` rows of the table and the head, ``n_held`` of
``n_routed`` experts (``keras/layers/moe.DroplessExperts``). Each layer
is rematerialised for the backward pass, which keeps its input and the
values named in ``KEPT_NAMES``, each O(L) and dear to compute again:
the two results of the flash attention kernel (its output and its
logsumexp, 201 MB a layer at [1, 32, 8192, 128]), q, k and v as the
kernel reads them, the output projection's result, SwiGLU's two
pre-activations (the dense layer's and the shared expert's), and what
one layer type alone has (the sparse decoder's query product, gate
and MLP branch; the latent decoder's rotary key; the byte decoder's
chunk summaries and MLP input). The second forward runs the norms, the
narrow products a norm's backward reads and the routed experts' passes
again: no attention kernel and no wide product.
On the paths that hold [L, L] scores (the CPU's, short sequences)
nothing carries the kernel's two names; the others are kept there too.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.keras.layers.byte_decoder import (
    EVA_K_SUMMARY_NAME, EVA_V_SUMMARY_NAME, MLP_IN_NAME, ByteDecoderLayer)
from analytics_zoo_tpu.keras.layers.latent_decoder import (
    ATTENTION_K_ROT_NAME, LatentDecoderLayer)
from analytics_zoo_tpu.keras.layers.moe import (
    SWIGLU_GATE_NAME, SWIGLU_UP_NAME)
from analytics_zoo_tpu.keras.layers.sparse_decoder import (
    ATTENTION_GATE_NAME, ATTENTION_K_NAME, ATTENTION_OUT_NAME,
    ATTENTION_Q_NAME, ATTENTION_Q_PROJ_NAME, ATTENTION_V_NAME, MLP_OUT_NAME,
    RMSNorm, SparseDecoderLayer)
from analytics_zoo_tpu.models.common import ZooModel, register_model
from analytics_zoo_tpu.ops.pallas_attention import (
    FLASH_LSE_NAME, FLASH_OUT_NAME)


def next_token_loss(logits, labels):
    """Mean over positions of the cross-entropy of ``labels`` (each
    position's next token) under float32 ``logits`` [B, L, V]."""
    logits = logits.astype(jnp.float32)
    labels = labels.astype(jnp.int32)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def _ids(x):
    return (x["input_ids"] if isinstance(x, dict) else x).astype(jnp.int32)


def _nll_ahead(logits, tokens, first: int):
    """Cross-entropy of head ``i`` at position ``t`` on
    ``tokens[t + first + i]``: ([B, L, n] float32, the [L, n] mask of
    the pairs whose target lies in the row)."""
    l, n = logits.shape[1:3]
    ahead = jnp.arange(l)[:, None] + first + jnp.arange(n)[None]
    targets = tokens[:, jnp.minimum(ahead, l - 1)]             # [B, L, n]
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked, ahead < l


def multi_byte_loss(logits, labels):
    """Cross-entropy of ``n`` heads that look 1..n positions ahead:
    float32 ``logits`` [B, L, n, V], head ``i`` at position ``t``
    predicting ``labels[t + i]`` (``labels`` [B, L] is each position's
    next token, so that is token ``t + 1 + i``). The mean over the heads
    and over the positions whose target lies in the row
    (``t + i < L``), every such pair counting the same."""
    nll, inside = _nll_ahead(logits.astype(jnp.float32),
                             labels.astype(jnp.int32), 0)
    return jnp.sum(jnp.where(inside, nll, 0.0)) / (
        logits.shape[0] * jnp.sum(inside))


def _embed(module, x, init_std: float = 0.02, dtype=None):
    """Token ids -> [B, L, d] in ``dtype`` (``module.dtype`` where none
    is given), times sqrt(d) where the module scales its embeddings."""
    d = module.hidden_size
    dtype = module.dtype if dtype is None else dtype
    h = nn.Embed(module.vocab, d, name="embed",
                 embedding_init=nn.initializers.normal(init_std))(
        _ids(x)).astype(dtype)
    if module.scale_embedding:
        h = h * np.sqrt(d).astype(dtype)
    return h


# What the backward pass of a rematerialised layer finds kept, beside
# the layer's input: values that are cheap to hold and dear to compute
# again (docs/kernels.md "Named results for a caller that
# rematerialises" has each one's bytes and price). Each name is defined
# beside the value it names; a layer keeps those of them it carries.
KEPT_NAMES = (
    FLASH_OUT_NAME, FLASH_LSE_NAME,
    SWIGLU_GATE_NAME, SWIGLU_UP_NAME,
    ATTENTION_Q_NAME, ATTENTION_K_NAME, ATTENTION_V_NAME,
    ATTENTION_K_ROT_NAME, EVA_K_SUMMARY_NAME, EVA_V_SUMMARY_NAME,
    ATTENTION_Q_PROJ_NAME, ATTENTION_GATE_NAME, ATTENTION_OUT_NAME,
    MLP_IN_NAME, MLP_OUT_NAME)


def _rematerialised(layer_cls, names=KEPT_NAMES):
    """The backward pass keeps the layer's input and the values named
    in ``names`` (the flash kernel's two exist only where it ran);
    all else is computed again."""
    return nn.remat(
        layer_cls, static_argnums=(2,),
        policy=jax.checkpoint_policies.save_only_these_names(*names))


def _logits(module, h, heads: int = 1, init_std: float = 0.02,
            unit_offset: bool = False):
    """float32 logits [B, L, heads * vocab] of the final norm's output
    under the untied head."""
    h = RMSNorm(module.eps, module.dtype, unit_offset=unit_offset,
                name="final_norm")(h)
    head = module.param("head", nn.initializers.normal(init_std),
                        (module.hidden_size, heads * module.vocab))
    return jnp.dot(h, head.astype(module.dtype),
                   preferred_element_type=jnp.float32)


class SparseDecoderModule(nn.Module):
    vocab: int
    hidden_size: int
    layer_types: Sequence[str]
    n_dense_layers: int
    n_head: int
    n_kv_head: int
    head_dim: int
    window: int
    dense_width: int
    expert_width: int
    n_routed: int
    n_held: int
    first_held: int = 0
    top_k: int = 8
    route_scale: float = 1.0
    shared_width: int = 0
    bias_step: float = 0.001
    rope_theta: float = 10000.0
    eps: float = 1e-5
    scale_embedding: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = _embed(self, x)
        experts = dict(
            width=self.expert_width, n_routed=self.n_routed,
            n_held=self.n_held, first_held=self.first_held,
            top_k=self.top_k, route_scale=self.route_scale,
            shared_width=self.shared_width, bias_step=self.bias_step)
        layer = _rematerialised(SparseDecoderLayer)
        for i, kind in enumerate(self.layer_types):
            h = layer(
                kind=kind, n_head=self.n_head, n_kv_head=self.n_kv_head,
                head_dim=self.head_dim, window=self.window,
                dense_width=self.dense_width,
                experts=None if i < self.n_dense_layers else experts,
                rope_theta=self.rope_theta, eps=self.eps,
                dtype=self.dtype, name=f"layer_{i}")(h, train)
        return _logits(self, h)


class LatentDecoderModule(nn.Module):
    vocab: int
    hidden_size: int
    n_layers: int
    n_dense_layers: int
    n_head: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    latent_dim: int
    dense_width: int
    expert_width: int
    n_routed: int
    n_held: int
    first_held: int = 0
    top_k: int = 6
    route_scale: float = 1.0
    shared_width: int = 0
    bias_step: float = 0.001
    rope_theta: float = 10000.0
    eps: float = 1e-5
    scale_embedding: bool = False
    embed_init_std: float = 0.02
    router_init_std: Optional[float] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = _embed(self, x, self.embed_init_std)
        attention = dict(
            n_head=self.n_head, nope_dim=self.nope_dim,
            rope_dim=self.rope_dim, v_dim=self.v_dim,
            latent_dim=self.latent_dim, rope_theta=self.rope_theta)
        experts = dict(
            width=self.expert_width, n_routed=self.n_routed,
            n_held=self.n_held, first_held=self.first_held,
            top_k=self.top_k, route_scale=self.route_scale,
            shared_width=self.shared_width, bias_step=self.bias_step,
            router_init_std=self.router_init_std)
        layer = _rematerialised(LatentDecoderLayer)
        for i in range(self.n_layers):
            h = layer(
                attention=attention, dense_width=self.dense_width,
                experts=None if i < self.n_dense_layers else experts,
                eps=self.eps, dtype=self.dtype, name=f"layer_{i}")(h, train)
        return _logits(self, h)


class ByteDecoderModule(nn.Module):
    vocab: int
    hidden_size: int
    n_layers: int
    n_head: int
    head_dim: int
    window: int
    chunk: int
    dense_width: int
    n_pred_heads: int = 1
    rope_theta: float = 10000.0
    eps: float = 1e-5
    init_std: float = 0.02
    scale_embedding: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        # the residual stream is float32 from the table on
        h = _embed(self, x, self.init_std, jnp.float32)
        attention = dict(n_head=self.n_head, head_dim=self.head_dim,
                         window=self.window, chunk=self.chunk,
                         rope_theta=self.rope_theta)
        layer = _rematerialised(ByteDecoderLayer)
        for i in range(self.n_layers):
            h = layer(attention=attention, dense_width=self.dense_width,
                      eps=self.eps, init_std=self.init_std,
                      dtype=self.dtype, name=f"layer_{i}")(h, train)
        with jax.named_scope("multibyte_head"):
            logits = _logits(self, h, self.n_pred_heads, self.init_std,
                             unit_offset=True)
            logits = logits.reshape(logits.shape[:2]
                                    + (self.n_pred_heads, self.vocab))
            self._count(logits, _ids(x), train)
        return logits

    def _count(self, logits, ids, train: bool):
        """Collection ``counters`` (cumulative int32, published by the
        Estimator at each epoch's sync): each head's mean cross-entropy
        of the step in millionths of a nat, over the targets that lie
        in the row's own input (head ``i`` at ``t``: ``ids[t + 1 + i]``),
        and the steps counted: their growths' ratio is a head's mean
        loss over an epoch."""
        n = self.n_pred_heads
        counting = train and self.is_mutable_collection("counters")
        if not (counting or self.is_initializing()):
            return
        adds = {"multibyte_head_steps": jnp.ones((), jnp.int32),
                "multibyte_head_loss_micronats": jnp.zeros((n,), jnp.int32)}
        if counting:
            nll, inside = _nll_ahead(jax.lax.stop_gradient(logits), ids, 1)
            mean = jnp.sum(jnp.where(inside, nll, 0.0), (0, 1)) / jnp.maximum(
                ids.shape[0] * jnp.sum(inside, 0), 1)
            adds["multibyte_head_loss_micronats"] = jnp.round(
                1e6 * mean).astype(jnp.int32)
        for name, add in adds.items():
            counter = self.variable(
                "counters", name,
                lambda a=add: jnp.zeros(a.shape, jnp.int32))
            if counting:
                counter.value = counter.value + add


class _DecoderLM(ZooModel):
    """fit expects x = {"input_ids": [B, L]} (or the array) and
    y = [B, L], each position's next token; predict returns float32
    logits [B, L, vocab]."""

    default_loss = staticmethod(next_token_loss)
    default_optimizer = "adam"
    default_metrics = ()

    def _example_input(self):
        return {"input_ids": np.zeros((1, 16), np.int32)}


@register_model
class SparseDecoderLM(_DecoderLM):
    """A decoder of ``SparseDecoderLayer`` blocks (window and full
    grouped-KV attention by ``layer_types``, four norms, an output
    gate); ``_DecoderLM``'s contract."""

    def __init__(self, vocab: int, hidden_size: int,
                 layer_types: Sequence[str], n_dense_layers: int,
                 n_head: int, n_kv_head: int, head_dim: int, window: int,
                 dense_width: int, expert_width: int, n_routed: int,
                 n_held: int, first_held: int = 0, top_k: int = 8,
                 route_scale: float = 1.0, n_shared: int = 1,
                 bias_step: float = 0.001, rope_theta: float = 10000.0,
                 eps: float = 1e-5, scale_embedding: bool = True,
                 dtype: str = "float32"):
        super().__init__(
            vocab=vocab, hidden_size=hidden_size,
            layer_types=list(layer_types), n_dense_layers=n_dense_layers,
            n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
            window=window, dense_width=dense_width,
            expert_width=expert_width, n_routed=n_routed, n_held=n_held,
            first_held=first_held, top_k=top_k, route_scale=route_scale,
            n_shared=n_shared, bias_step=bias_step, rope_theta=rope_theta,
            eps=eps, scale_embedding=scale_embedding, dtype=dtype)

    def _build_module(self):
        c = dict(self._config)
        c["shared_width"] = c.pop("n_shared") * c["expert_width"]
        c["layer_types"] = tuple(c["layer_types"])
        c["dtype"] = jnp.dtype(c["dtype"])
        return SparseDecoderModule(**c)


@register_model
class LatentDecoderLM(_DecoderLM):
    """A decoder of latent-attention blocks (``LatentDecoderLayer``):
    ``n_dense_layers`` dense layers, then routed experts with
    ``n_shared`` shared experts of ``expert_width`` beside them;
    ``_DecoderLM``'s contract. ``embed_init_std`` / ``router_init_std``:
    how a fresh model starts (the benchmark configuration's
    ``assumed.initialisation`` says why)."""

    def __init__(self, vocab: int, hidden_size: int, n_layers: int,
                 n_dense_layers: int, n_head: int, nope_dim: int,
                 rope_dim: int, v_dim: int, latent_dim: int,
                 dense_width: int, expert_width: int, n_routed: int,
                 n_held: int, first_held: int = 0, top_k: int = 6,
                 route_scale: float = 1.0, n_shared: int = 2,
                 bias_step: float = 0.001, rope_theta: float = 10000.0,
                 eps: float = 1e-5, scale_embedding: bool = False,
                 embed_init_std: float = 0.02,
                 router_init_std: Optional[float] = None,
                 dtype: str = "float32"):
        super().__init__(
            vocab=vocab, hidden_size=hidden_size, n_layers=n_layers,
            n_dense_layers=n_dense_layers, n_head=n_head,
            nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
            latent_dim=latent_dim, dense_width=dense_width,
            expert_width=expert_width, n_routed=n_routed, n_held=n_held,
            first_held=first_held, top_k=top_k, route_scale=route_scale,
            n_shared=n_shared, bias_step=bias_step, rope_theta=rope_theta,
            eps=eps, scale_embedding=scale_embedding,
            embed_init_std=embed_init_std,
            router_init_std=router_init_std, dtype=dtype)

    def _build_module(self):
        c = dict(self._config)
        c["shared_width"] = c.pop("n_shared") * c["expert_width"]
        c["dtype"] = jnp.dtype(c["dtype"])
        return LatentDecoderModule(**c)


@register_model
class ByteDecoderLM(_DecoderLM):
    """A dense decoder over bytes: ``n_layers`` ``ByteDecoderLayer``
    blocks (EVA attention over windows of ``window`` and chunks of
    ``chunk``) and ``n_pred_heads`` heads over the vocabulary. fit
    takes ``_DecoderLM``'s x and y = [B, L] (each position's next
    byte; the further heads' targets are y shifted); predict returns
    float32 logits [B, L, n_pred_heads, vocab]."""

    default_loss = staticmethod(multi_byte_loss)

    def __init__(self, vocab: int, hidden_size: int, n_layers: int,
                 n_head: int, head_dim: int, window: int, chunk: int,
                 dense_width: int, n_pred_heads: int = 1,
                 rope_theta: float = 10000.0, eps: float = 1e-5,
                 init_std: float = 0.02, scale_embedding: bool = False,
                 dtype: str = "float32"):
        super().__init__(
            vocab=vocab, hidden_size=hidden_size, n_layers=n_layers,
            n_head=n_head, head_dim=head_dim, window=window, chunk=chunk,
            dense_width=dense_width, n_pred_heads=n_pred_heads,
            rope_theta=rope_theta, eps=eps, init_std=init_std,
            scale_embedding=scale_embedding, dtype=dtype)

    def _build_module(self):
        c = dict(self._config)
        c["dtype"] = jnp.dtype(c["dtype"])
        return ByteDecoderModule(**c)
