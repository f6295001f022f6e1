"""Transformer and BERT layers.

The analog of ``TransformerLayer.scala`` (GPT-style decoder stack) and
``BERT.scala`` (ref: zoo/.../keras/layers/{TransformerLayer,BERT}.scala),
re-designed TPU-first: attention goes through ``ops.attention`` (Pallas
flash kernel on TPU, never materializing the [L, L] score matrix the
reference builds), all matmuls MXU-shaped. BERT's erf GELU is
``ops.activations.gelu_exact``: evaluated once a layer with its
derivative stored for the backward, where XLA left alone re-derives erf
inside each of ``ffn_out``'s three matmul fusions; the tanh ``"gelu"``
and ``relu`` are stock and fused by XLA.

North-star workload #4 (BERT-base fine-tune) builds on BERT here.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.keras.layers.base import KerasLayer
from analytics_zoo_tpu.ops.activations import gelu_exact
from analytics_zoo_tpu.ops.attention import packed_attention
from analytics_zoo_tpu.ops.dropout import Dropout

_zigzag_shape_warned = False


def _warn_zigzag_shape_once(l, seq_size):
    global _zigzag_shape_warned
    if not _zigzag_shape_warned:
        _zigzag_shape_warned = True
        from analytics_zoo_tpu.common.log import get_logger

        get_logger(__name__).warning(
            "ring_schedule=zigzag requested but seq_len %d is not "
            "divisible by 2*seq_axis_size (%d); falling back to the "
            "contiguous causal ring (~2x more attention compute)",
            l, 2 * seq_size)


class MultiHeadSelfAttention(nn.Module):
    """``seq_axis``: name of a mesh axis to shard the sequence over --
    when set (and the context mesh has that axis with size > 1 and no
    explicit mask), attention runs as exact ring attention over the
    axis (``parallel.ring_attention``), giving long-context sequence
    parallelism inside any model built on this layer; attention-prob
    dropout applies tile-wise inside the ring. Otherwise dispatches to
    the flash/jnp kernels."""

    hidden_size: int
    n_head: int
    attn_dropout: float = 0.0
    causal: bool = False
    dtype: Any = jnp.float32  # compute dtype; params stay fp32
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, mask=None, key_padding_mask=None,
                 train: bool = False):
        b, l, _ = x.shape
        hd = self.hidden_size // self.n_head
        # fused projection with kernel [H, 3, H]: one MXU matmul, and
        # the q/k/v sections sit on their own axis so tensor-parallel
        # sharding of the last dim stays head-aligned (megatron layout;
        # a flat [H, 3H] kernel puts tp shard boundaries across the
        # q|k|v concatenation)
        qkv = nn.DenseGeneral((3, self.hidden_size), dtype=self.dtype,
                              name="qkv")(x)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]

        out = None
        if (self.seq_axis is not None and mask is None
                and key_padding_mask is None):
            from analytics_zoo_tpu.parallel.mesh import (
                default_mesh, mesh_axis_size)
            from analytics_zoo_tpu.parallel.ring_attention import (
                ring_attention)

            mesh = default_mesh()
            seq_size = mesh_axis_size(mesh, self.seq_axis)
            data_size = mesh_axis_size(
                mesh, "data") if "data" in mesh.axis_names else 1
            # shard_map preconditions: both sharded dims must divide --
            # fall back to the dense path like the mask/dropout cases
            if seq_size > 1 and l % seq_size == 0 and b % data_size == 0:
                ring_rng = (self.make_rng("dropout")
                            if train and self.attn_dropout > 0 else None)
                # ring layout [B, L, H, D]; shard_map nests inside the
                # outer jit and reshards q/k/v along the seq axis.
                # Prob-dropout applies tile-wise inside the ring (exact;
                # see ring_attention's numerator-only masking). Causal
                # stacks take the zigzag schedule when shapes divide:
                # same exact softmax, ~2x less compute (ring_schedule
                # config: auto|zigzag|contiguous)
                from analytics_zoo_tpu.common.config import get_config
                from analytics_zoo_tpu.parallel.ring_attention import (
                    zigzag_ring_attention)

                schedule = get_config().get("zoo.ops.ring_schedule")
                if schedule not in ("auto", "zigzag", "contiguous"):
                    raise ValueError(
                        f"zoo.ops.ring_schedule must be auto|zigzag|"
                        f"contiguous, got {schedule!r}")
                divides = l % (2 * seq_size) == 0
                if schedule == "zigzag" and self.causal and not divides:
                    _warn_zigzag_shape_once(l, seq_size)
                use_zigzag = (self.causal
                              and schedule in ("auto", "zigzag")
                              and divides)
                ring_fn = (zigzag_ring_attention if use_zigzag
                           else partial(ring_attention,
                                        causal=self.causal))
                out = ring_fn(
                    q.reshape(b, l, self.n_head, hd),
                    k.reshape(b, l, self.n_head, hd),
                    v.reshape(b, l, self.n_head, hd),
                    mesh, axis_name=self.seq_axis,
                    dropout_rate=self.attn_dropout if train else 0.0,
                    dropout_rng=ring_rng,
                ).reshape(b, l, self.hidden_size)
        if out is None:
            rng = (self.make_rng("dropout")
                   if train and self.attn_dropout > 0 else None)
            from analytics_zoo_tpu.parallel.mesh import (
                config_axis, traced_mesh)

            # q, k, v stay as the projection wrote them: the short-row
            # kernels read that layout (and are told the mesh: GSPMD
            # cannot partition them), every other path transposes
            out = packed_attention(
                q, k, v, self.n_head, mask=mask,
                key_padding_mask=key_padding_mask, causal=self.causal,
                dropout_rate=self.attn_dropout if train else 0.0,
                dropout_rng=rng, mesh=traced_mesh(),
                batch_axis=config_axis("data"),
                head_axis=config_axis("model"))
        return nn.Dense(self.hidden_size, dtype=self.dtype,
                        name="proj")(out)


class TransformerBlock(nn.Module):
    """Pre/post-LN encoder-or-decoder block (the reference uses post-LN,
    ref: TransformerLayer.scala block)."""

    hidden_size: int
    n_head: int
    intermediate_size: int
    hidden_dropout: float = 0.1
    attn_dropout: float = 0.1
    causal: bool = False
    activation: str = "gelu"
    ln_eps: float = 1e-5
    dtype: Any = jnp.float32
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, mask=None, key_padding_mask=None,
                 train: bool = False):
        # "gelu" keeps the tanh approximation (GPT lineage + saved
        # checkpoints); "gelu_exact" is the erf form BERT/torch use --
        # the two diverge ~1e-3, so each model family pins its own
        if self.activation == "gelu_exact":
            act = gelu_exact
        elif self.activation == "gelu":
            act = jax.nn.gelu
        else:
            act = jax.nn.relu
        attn = MultiHeadSelfAttention(
            self.hidden_size, self.n_head, attn_dropout=self.attn_dropout,
            causal=self.causal, dtype=self.dtype,
            seq_axis=self.seq_axis, name="attention")(
                x, mask=mask, key_padding_mask=key_padding_mask,
                train=train)
        attn = Dropout(self.hidden_dropout, deterministic=not train)(attn)
        x = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype,
                         name="ln_attn")(x + attn)
        h = nn.Dense(self.intermediate_size, dtype=self.dtype,
                     name="ffn_in")(x)
        h = act(h)
        h = nn.Dense(self.hidden_size, dtype=self.dtype,
                     name="ffn_out")(h)
        h = Dropout(self.hidden_dropout, deterministic=not train)(h)
        return nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype,
                            name="ln_ffn")(x + h)


class TransformerModule(nn.Module):
    """GPT-style decoder stack over token ids
    (ref: TransformerLayer.scala)."""

    vocab: int
    seq_len: int
    hidden_size: int = 768
    n_head: int = 12
    n_block: int = 12
    intermediate_size: Optional[int] = None
    hidden_dropout: float = 0.1
    attn_dropout: float = 0.1
    output_all_block: bool = False
    dtype: Any = jnp.float32
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        ids = x.astype(jnp.int32)
        b, l = ids.shape
        tok = nn.Embed(self.vocab, self.hidden_size, name="token_embed")(ids)
        pos = self.param("position_embed",
                         nn.initializers.normal(0.01),
                         (self.seq_len, self.hidden_size))
        h = tok + pos[None, :l]
        h = Dropout(self.hidden_dropout, deterministic=not train)(h)
        outs = []
        inter = self.intermediate_size or 4 * self.hidden_size
        for i in range(self.n_block):
            h = TransformerBlock(
                self.hidden_size, self.n_head, inter,
                hidden_dropout=self.hidden_dropout,
                attn_dropout=self.attn_dropout, causal=True,
                dtype=self.dtype, seq_axis=self.seq_axis,
                name=f"block_{i}")(h, train=train)
            outs.append(h)
        return tuple(outs) if self.output_all_block else h


class BERTModule(nn.Module):
    """BERT encoder (ref: BERT.scala): token + position + segment
    embeddings, post-LN encoder blocks, tanh pooler over [CLS].

    Input: dict with ``input_ids`` [B, L]; optional ``token_type_ids``
    [B, L] and ``attention_mask`` [B, L] (1 = real token).
    Returns (sequence_output [B, L, H], pooled_output [B, H]).
    """

    vocab: int
    hidden_size: int = 768
    n_block: int = 12
    n_head: int = 12
    intermediate_size: int = 3072
    max_position_len: int = 512
    type_vocab: int = 2
    hidden_dropout: float = 0.1
    attn_dropout: float = 0.1
    dtype: Any = jnp.float32
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        if isinstance(x, dict):
            ids = x["input_ids"].astype(jnp.int32)
            segs = x.get("token_type_ids")
            attn_mask = x.get("attention_mask")
        else:
            ids, segs, attn_mask = x.astype(jnp.int32), None, None
        b, l = ids.shape
        h = nn.Embed(self.vocab, self.hidden_size, name="token_embed")(ids)
        pos = self.param("position_embed", nn.initializers.normal(0.02),
                         (self.max_position_len, self.hidden_size))
        h = h + pos[None, :l]
        if segs is not None:
            h = h + nn.Embed(self.type_vocab, self.hidden_size,
                             name="segment_embed")(segs.astype(jnp.int32))
        h = nn.LayerNorm(epsilon=1e-12, name="embed_ln")(h)
        h = Dropout(self.hidden_dropout, deterministic=not train)(h)

        # padding mask stays [B, L]: flash-kernel-compatible (lowered to
        # segment ids) instead of a materialized 4-D mask
        for i in range(self.n_block):
            h = TransformerBlock(
                self.hidden_size, self.n_head, self.intermediate_size,
                hidden_dropout=self.hidden_dropout,
                attn_dropout=self.attn_dropout, causal=False,
                activation="gelu_exact", ln_eps=1e-12,
                dtype=self.dtype, seq_axis=self.seq_axis,
                name=f"encoder_{i}")(h, key_padding_mask=attn_mask,
                                     train=train)
        pooled = jnp.tanh(nn.Dense(self.hidden_size, name="pooler")
                          (h[:, 0]))
        return h, pooled


class TransformerLayerKL(KerasLayer):
    """Keras-layer wrapper for the decoder stack
    (ref: TransformerLayer.scala companion object init)."""

    def __init__(self, vocab: int, seq_len: int, hidden_size: int = 768,
                 n_head: int = 12, n_block: int = 12, **kwargs):
        extra = {k: kwargs.pop(k) for k in list(kwargs)
                 if k in ("intermediate_size", "hidden_dropout",
                          "attn_dropout", "output_all_block")}
        super().__init__(**kwargs)
        self._cfg = dict(vocab=vocab, seq_len=seq_len,
                         hidden_size=hidden_size, n_head=n_head,
                         n_block=n_block, **extra)

    def _make_module(self):
        return TransformerModule(**self._cfg)


class BERTKL(KerasLayer):
    """Keras-layer wrapper for BERT (ref: BERT.scala companion init)."""

    def __init__(self, vocab: int, hidden_size: int = 768,
                 n_block: int = 12, n_head: int = 12,
                 intermediate_size: int = 3072,
                 max_position_len: int = 512, **kwargs):
        extra = {k: kwargs.pop(k) for k in list(kwargs)
                 if k in ("type_vocab", "hidden_dropout", "attn_dropout")}
        super().__init__(**kwargs)
        self._cfg = dict(vocab=vocab, hidden_size=hidden_size,
                         n_block=n_block, n_head=n_head,
                         intermediate_size=intermediate_size,
                         max_position_len=max_position_len, **extra)

    def _make_module(self):
        return BERTModule(**self._cfg)


# public names matching the reference layer files
TransformerLayer = TransformerLayerKL
BERT = BERTKL
