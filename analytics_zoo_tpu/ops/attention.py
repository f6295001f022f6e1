"""Attention dispatch: Pallas flash kernels on TPU, jnp einsum on CPU.

Replaces the reference's O(L^2)-materialized attention
(ref: zoo/.../keras/layers/TransformerLayer.scala attn -- builds the full
[B, H, L, L] score matrix through BigDL ops). On TPU the flash kernels
never materialize scores in HBM:

- head_dim % 64 == 0 -> the framework's own Pallas kernel
  (``pallas_attention.pallas_flash_attention_fwd``, exact custom_vjp;
  covers BERT-base head_dim 64 since r5);
- otherwise -> the stock fused fwd+bwd kernel, which also serves
  key-padding masks (lowered to segment ids).

The jnp reference path handles CPU, arbitrary 4-D masks, and attention
dropout (flash kernels don't support prob dropout -- same trade-off every
flash implementation makes).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def reference_attention(q, k, v, mask=None, causal: bool = False,
                        scale: Optional[float] = None):
    """Exact jnp attention; the single source of truth the Pallas kernels
    are tested against and the custom_vjp backward recomputes through."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        lq, lk = q.shape[2], k.shape[2]
        cm = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        logits = jnp.where(cm[None, None], logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask.astype(bool), logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _einsum_attention(q, k, v, mask=None, causal: bool = False,
                      scale: Optional[float] = None):
    """MXU-shaped exact attention: scores accumulate in f32 (softmax
    numerics), probabilities drop back to the value dtype so the PV
    matmul rides the fast bf16 MXU path instead of a full-precision
    one. Same math as ``reference_attention`` (golden-tested against
    it); this is the variant the dispatcher uses."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        lq, lk = q.shape[2], k.shape[2]
        cm = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        logits = jnp.where(cm[None, None], logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask.astype(bool), logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _platform(q) -> str:
    """Platform the attention will run on: a concrete array's own
    device, else (tracers under jit, host arrays) the default backend."""
    if isinstance(q, jax.Array) and not isinstance(q, jax.core.Tracer):
        return next(iter(q.devices())).platform
    return jax.default_backend()


def dot_product_attention(q, k, v, mask=None, key_padding_mask=None,
                          causal: bool = False,
                          scale: Optional[float] = None,
                          dropout_rate: float = 0.0, dropout_rng=None):
    """q,k,v: [B, H, L, D]. ``mask``: arbitrary [B, H, Lq, Lk]-broadcastable
    (1 = attend; forces the jnp path). ``key_padding_mask``: [B, Lk] with
    1 = real token -- flash-compatible (lowered to segment ids).
    Returns [B, H, Lq, D].

    The path taken names itself in every op's ``op_name`` (and so in a
    device trace): ``attention_flash`` (this repo's Pallas kernel),
    ``attention_stock_pallas``, ``attention_einsum`` or
    ``attention_reference``."""
    d = q.shape[-1]
    l, lk = q.shape[2], k.shape[2]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    if causal and l > lk:
        # with the bottom-right-aligned diagonal the first lq-lk rows
        # attend to nothing; every backend would return garbage for them
        raise ValueError("causal attention requires len(q) <= len(kv)")

    from analytics_zoo_tpu.common.config import get_config

    cfg = get_config()
    impl = cfg.get("zoo.ops.attention_impl")
    if impl == "auto" and max(l, lk) <= int(
            cfg.get("zoo.ops.attention_flash_min_seq")):
        # short sequences: the [L, L] scores are small enough that
        # XLA's fused batched-matmul attention beats the blockwise
        # kernels (measured ~2x on v5e at BERT-base L=384/d=64)
        impl = "einsum"
    # the einsum path is the CPU's; any other platform compiles the
    # kernels (or fails loudly), whatever it calls itself
    flash_ok = (impl != "einsum"
                and mask is None and dropout_rate == 0.0
                and _platform(q) != "cpu"
                and l % 128 == 0 and lk % 128 == 0
                and not (causal and l > lk))
    if flash_ok and d % 64 == 0:
        from analytics_zoo_tpu.ops.pallas_attention import (
            pallas_flash_attention_fwd)

        if key_padding_mask is None:
            with jax.named_scope("attention_flash"):
                return pallas_flash_attention_fwd(q, k, v, causal, scale)
        # padding masks fall through to the stock kernel's segment ids
    # the stock kernel's causal mask is top-left aligned (no cross-length
    # offset), so it only agrees with reference_attention when lq == lk
    if flash_ok and d <= 128 and (not causal or l == lk):
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            SegmentIds, flash_attention)

        seg = None
        if key_padding_mask is not None:
            kv_seg = key_padding_mask.astype(jnp.int32)
            q_seg = (kv_seg if lk == l
                     else jnp.ones((q.shape[0], l), jnp.int32))
            seg = SegmentIds(q=q_seg, kv=kv_seg)
        with jax.named_scope("attention_stock_pallas"):
            return flash_attention(q, k, v, segment_ids=seg, causal=causal,
                                   sm_scale=scale)

    if key_padding_mask is not None:
        pm = key_padding_mask[:, None, None, :].astype(bool)
        mask = pm if mask is None else (mask.astype(bool) & pm)
    if dropout_rate == 0.0:
        with jax.named_scope("attention_einsum"):
            return _einsum_attention(q, k, v, mask=mask, causal=causal,
                                     scale=scale)
    with jax.named_scope("attention_reference"):
        if dropout_rate > 0.0 and dropout_rng is not None:
            # dropout needs the materialized probs; inline the reference
            # math
            logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
            if causal:
                cm = jnp.tril(jnp.ones((l, lk), bool), k=lk - l)
                logits = jnp.where(cm[None, None], logits, NEG_INF)
            if mask is not None:
                logits = jnp.where(mask.astype(bool), logits, NEG_INF)
            probs = jax.nn.softmax(logits, axis=-1)
            keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                        probs.shape)
            probs = probs * keep / (1.0 - dropout_rate)
            return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        return reference_attention(q, k, v, mask=mask, causal=causal,
                                   scale=scale)
