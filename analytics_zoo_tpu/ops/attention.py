"""Attention dispatch. One entry point, ``dot_product_attention``
(and ``packed_attention``, the same call on operands laid out
``[B, L, H x D]``), and five paths, chosen by ``attention_path`` from
the platform and the call's shapes alone; the one taken names itself
in ``op_name``:

- ``attention_flash`` -- the framework's own blockwise Pallas kernels
  (``pallas_attention.pallas_flash_attention_fwd``, exact custom_vjp):
  off the CPU, no mask or dropout, both lengths multiples of 128,
  head_dim (and the values' width) a multiple of 64, and a sequence
  longer than ``FLASH_MIN_SEQ``. The only path that serves a causal
  ``window``, K/V with fewer heads than Q, values narrower than the
  keys and a key head shared by every query head (``k_shared``)
  without materialising any of them (docs/kernels.md); a window call
  is named ``attention_flash_window``, one whose values' width is not
  the queries' (latent attention) ``attention_flash_latent``;
- ``attention_flash_short`` -- the framework's short-row kernels
  (``pallas_short_attention``; the whole row of a head in one tile,
  several heads a grid step, operands ``[B, L, H x 64]`` where the
  projection wrote them): off the CPU, plain self-attention (no mask,
  dropout, ``causal``, window or shared key head; as many KV heads as
  query heads, an even number of them) with heads of 64 and one length,
  a multiple of 128 up to ``FLASH_MIN_SEQ`` (BERT at L384);
- ``attention_stock_pallas`` -- JAX's fused fwd+bwd kernel: the same
  conditions with one head_dim <= 128, for key-padding masks (lowered
  to segment ids) and head sizes the owned kernel refuses;
- ``attention_einsum`` -- batched matmuls with an f32 softmax and the
  [L, L] scores in HBM: the CPU's path, arbitrary 4-D masks, and on
  the chip the short sequences the short-row kernels refuse. With a
  window: ``attention_einsum_window``;
- ``attention_reference`` -- the same in plain ``jnp``, for attention
  dropout (flash kernels do not support it).

``eva_attention`` is a second entry point under the same rule: exact
causal attention inside each window and, in the same softmax, the
chunk summaries of every earlier window (``attention_<path>_eva``;
the owned kernels off the CPU, else ``einsum`` with the joint scores
held).

Replaces the reference's O(L^2)-materialized attention
(ref: zoo/.../keras/layers/TransformerLayer.scala attn).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

NEG_INF = -1e30


def _repeat_kv(q, k, v):
    """K/V with fewer heads than Q, repeated to Q's (query head n reads
    KV head ``n // group``): what the paths that materialise scores do;
    the owned flash kernel reads them through its index maps instead."""
    if k.shape[1] == q.shape[1]:
        return k, v
    group, rest = divmod(q.shape[1], k.shape[1])
    if rest:
        raise ValueError(f"{q.shape[1]} query heads do not divide over "
                         f"{k.shape[1]} KV heads")
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


def _causal_keep(lq: int, lk: int, window: Optional[int]):
    """[lq, lk] bool: bottom-right-aligned causal mask, and with
    ``window`` only the ``window`` newest keys of each row."""
    keep = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
    if window is not None:
        keep &= ~jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq - window)
    return keep


def reference_attention(q, k, v, mask=None, causal: bool = False,
                        scale: Optional[float] = None,
                        window: Optional[int] = None,
                        dropout_rate: float = 0.0, dropout_rng=None):
    """Exact jnp attention; the single source of truth the Pallas kernels
    are tested against and the custom_vjp backward recomputes through.
    With a ``dropout_rng``, attention dropout on the materialised
    probabilities (no kernel supports it)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    k, v = _repeat_kv(q, k, v)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        cm = _causal_keep(q.shape[2], k.shape[2], window)
        logits = jnp.where(cm[None, None], logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask.astype(bool), logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                    probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _einsum_attention(q, k, v, mask=None, causal: bool = False,
                      scale: Optional[float] = None,
                      window: Optional[int] = None):
    """MXU-shaped exact attention: scores accumulate in f32 (softmax
    numerics), probabilities drop back to the value dtype so the PV
    matmul rides the fast bf16 MXU path instead of a full-precision
    one. Same math as ``reference_attention`` (golden-tested against
    it); this is the variant the dispatcher uses."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    k, v = _repeat_kv(q, k, v)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        cm = _causal_keep(q.shape[2], k.shape[2], window)
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


# The border between the two owned kernels: up to it a head's whole
# [L, L] tile fits VMEM and the short-row kernels serve the shapes they
# take (``attention_path``), past it the blockwise kernels walk
# kv-blocks; every other call up to it keeps the [L, L] scores in HBM
# (einsum). Measured on the v5e (docs/kernels.md "Measured crossover",
# PR 36: h12 d64 bf16, forward + backward from the fused projection's
# output, tokens held at 32 x 384): at L384 einsum 2.23 ms, the
# blockwise kernel 2.09 (a short head is one grid step: flat in L),
# the short-row kernels 1.04; at 512 3.24 / 2.11 / 1.19; at 128 they
# tie einsum (0.82 / 0.80); at 1,024 the blockwise kernel wins 2.1x
# (2.91 against 6.14). The benchmark has a cell on each side: BERT at
# L384 (short rows) and Trinity-Mini at L8192 (blockwise).
FLASH_MIN_SEQ = 512


def attention_path(platform: str, lq: int, lk: int, head_dim: int,
                   q_heads: int, kv_heads: int, *,
                   value_dim: Optional[int] = None, mask: bool = False,
                   key_padding_mask: bool = False, dropout: bool = False,
                   causal: bool = False, window: bool = False,
                   k_shared: bool = False) -> str:
    """Which path serves a call: ``flash``, ``flash_short``,
    ``stock_pallas``, ``einsum`` or ``reference``. A pure function of
    what the call site can observe: the platform, the shapes
    (``value_dim`` is the values' width where it is not ``head_dim``),
    and which of a 4-D mask, a key-padding mask, dropout, ``causal``, a
    window and a shared key head are present."""
    value_dim = head_dim if value_dim is None else value_dim
    # plain self-attention over short rows: the one shape the short-row
    # kernels are written for (two heads of 64 share a lane tile)
    if (platform != "cpu" and lq == lk and lq % 128 == 0
            and lq <= FLASH_MIN_SEQ and head_dim == value_dim == 64
            and kv_heads == q_heads and q_heads % 2 == 0
            and not (mask or key_padding_mask or dropout or causal
                     or window or k_shared)):
        return "flash_short"
    # the einsum path is the CPU's; any other platform compiles the
    # kernels (or fails loudly), whatever it calls itself
    kernels = (platform != "cpu" and max(lq, lk) > FLASH_MIN_SEQ
               and lq % 128 == 0 and lk % 128 == 0
               and not mask and not dropout)
    if (kernels and head_dim % 64 == 0 and value_dim % 64 == 0
            and not key_padding_mask):
        return "flash"
    # padding masks ride the stock kernel's segment ids. Its causal mask
    # is top-left aligned (no cross-length offset), so it only agrees
    # with reference_attention when lq == lk; it knows neither a window
    # nor grouped heads
    if (kernels and head_dim <= 128 and value_dim == head_dim
            and (not causal or lq == lk)
            and not window and kv_heads == q_heads):
        return "stock_pallas"
    return "reference" if dropout else "einsum"


def dot_product_attention(q, k, v, mask=None, key_padding_mask=None,
                          causal: bool = False,
                          scale: Optional[float] = None,
                          dropout_rate: float = 0.0, dropout_rng=None,
                          window: Optional[int] = None, k_shared=None):
    """q: [B, H, L, D]; k: [B, H_kv, Lk, D], v: [B, H_kv, Lk, Dv] with
    ``H_kv`` dividing ``H`` (query head n reads KV head
    ``n // (H / H_kv)``); ``Dv`` need not be ``D``. ``k_shared``
    [B, 1, Lk, Ds]: one more key head that the last ``Ds`` columns of
    every query head contract with, beside that head's own ``k``
    [B, H, Lk, D - Ds] (latent attention's rotary key). ``mask``:
    arbitrary [B, H, Lq, Lk]-broadcastable (1 = attend; forces the jnp
    path). ``key_padding_mask``: [B, Lk] with 1 = real token --
    flash-compatible (lowered to segment ids). ``window`` (with
    ``causal``): row i reads only keys ``i - window < j <= i``.
    Returns [B, H, Lq, Dv].

    The path taken (``attention_path``) names itself in every op's
    ``op_name`` (and so in a device trace): ``attention_flash`` (this
    repo's Pallas kernel), ``attention_stock_pallas``,
    ``attention_einsum`` or ``attention_reference``; a call whose
    values' width is not the queries' adds ``_latent``, a window call
    ``_window``."""
    d = q.shape[-1]
    l, lk = q.shape[2], k.shape[2]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    if causal and l > lk:
        # with the bottom-right-aligned diagonal the first lq-lk rows
        # attend to nothing; every backend would return garbage for them
        raise ValueError("causal attention requires len(q) <= len(kv)")
    if window is not None and not causal:
        raise ValueError("a window needs causal=True")

    path = attention_path(
        _platform(q), l, lk, d, q.shape[1], k.shape[1],
        value_dim=v.shape[-1], mask=mask is not None,
        key_padding_mask=key_padding_mask is not None,
        dropout=dropout_rate != 0.0, causal=causal,
        window=window is not None, k_shared=k_shared is not None)
    scope = jax.named_scope(
        f"attention_{path}" + ("" if v.shape[-1] == d else "_latent")
        + ("" if window is None else "_window"))
    if path == "flash":
        from analytics_zoo_tpu.ops.pallas_attention import (
            pallas_flash_attention_fwd)

        with scope:
            return pallas_flash_attention_fwd(q, k, v, causal, scale,
                                              None, None, window, k_shared)
    if path == "flash_short":
        # a heads-first caller pays the transposes ``packed_attention``'s
        # callers do not
        with scope:
            return _heads_first(_short_attention(
                *map(_packed, (q, k, v)), q.shape[1], scale), q.shape[1])
    if k_shared is not None:
        # the paths that hold [L, L] scores hold the joined keys too
        with scope:
            k = jnp.concatenate([k, jnp.broadcast_to(
                k_shared, k.shape[:-1] + k_shared.shape[-1:])], axis=-1)
    if path == "stock_pallas":
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            SegmentIds, flash_attention)

        seg = None
        if key_padding_mask is not None:
            kv_seg = key_padding_mask.astype(jnp.int32)
            q_seg = (kv_seg if lk == l
                     else jnp.ones((q.shape[0], l), jnp.int32))
            seg = SegmentIds(q=q_seg, kv=kv_seg)
        with scope:
            return flash_attention(q, k, v, segment_ids=seg, causal=causal,
                                   sm_scale=scale)
    if key_padding_mask is not None:
        pm = key_padding_mask[:, None, None, :].astype(bool)
        mask = pm if mask is None else (mask.astype(bool) & pm)
    with scope:
        if path == "einsum":
            return _einsum_attention(q, k, v, mask=mask, causal=causal,
                                     scale=scale, window=window)
        return reference_attention(q, k, v, mask=mask, causal=causal,
                                   scale=scale, window=window,
                                   dropout_rate=dropout_rate,
                                   dropout_rng=dropout_rng)


def _heads_first(t, heads: int):
    """[B, L, heads x D] -> [B, heads, L, D]."""
    b, l, width = t.shape
    return t.reshape(b, l, heads, width // heads).transpose(0, 2, 1, 3)


def _packed(t):
    """[B, heads, L, D] -> [B, L, heads x D]."""
    b, heads, l, d = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b, l, heads * d)


def _short_attention(q, k, v, heads: int, scale: float, mesh=None,
                     batch_axis: Optional[str] = None,
                     head_axis: Optional[str] = None):
    """The short-row kernels on ``[B, L, heads x 64]``. A ``pallas_call``
    is opaque to the partitioner, which would gather its operands onto
    every device and run it whole on each: under a ``mesh`` of several
    devices the call is a ``shard_map``, the batch over ``batch_axis``
    and the head pairs over ``head_axis`` where the mesh has the axis
    and it divides them, ``L`` and a pair's 128 lanes whole. Each device
    runs the kernels on its own rows and heads; no collective."""
    from analytics_zoo_tpu.ops.pallas_short_attention import (
        pallas_short_attention)
    from analytics_zoo_tpu.parallel.mesh import shard_map

    if mesh is None or mesh.size == 1:
        return pallas_short_attention(q, k, v, heads, scale)

    def over(axis: Optional[str], n: int):
        fits = axis in mesh.axis_names and n % mesh.shape[axis] == 0
        return axis if fits else None

    rows, pairs = over(batch_axis, q.shape[0]), over(head_axis, heads // 2)
    spec = PartitionSpec(rows, None, pairs)
    local = heads // (mesh.shape[pairs] if pairs else 1)
    return shard_map(
        lambda q, k, v: pallas_short_attention(q, k, v, local, scale),
        mesh, in_specs=(spec,) * 3, out_specs=spec)(q, k, v)


def packed_attention(q, k, v, heads: int, mask=None, key_padding_mask=None,
                     causal: bool = False, scale: Optional[float] = None,
                     dropout_rate: float = 0.0, dropout_rng=None, mesh=None,
                     batch_axis: str = "data", head_axis: str = "model"):
    """``dot_product_attention`` on q, k, v ``[B, L, heads x D]``, each
    head's columns side by side as a fused projection writes them;
    returns ``[B, L, heads x D]``, where the output projection reads
    it. Where ``attention_path`` answers ``flash_short`` the kernels
    read and write this layout and nothing is transposed (``mesh``: the
    mesh the traced program is partitioned over and its ``batch_axis``
    / ``head_axis``, see ``_short_attention``); every other path is
    ``dot_product_attention`` between the heads-first transposes."""
    d = q.shape[-1] // heads
    path = attention_path(
        _platform(q), q.shape[1], k.shape[1], d, heads, heads,
        value_dim=v.shape[-1] // heads, mask=mask is not None,
        key_padding_mask=key_padding_mask is not None,
        dropout=dropout_rate != 0.0, causal=causal)
    if path == "flash_short":
        with jax.named_scope("attention_flash_short"):
            return _short_attention(
                q, k, v, heads,
                scale if scale is not None else 1.0 / np.sqrt(d), mesh,
                batch_axis, head_axis)
    return _packed(dot_product_attention(
        *(_heads_first(t, heads) for t in (q, k, v)), mask=mask,
        key_padding_mask=key_padding_mask, causal=causal, scale=scale,
        dropout_rate=dropout_rate, dropout_rng=dropout_rng))


def _eva_keep(l: int, window: int, per_window: int):
    """[l, l / window * per_window + l] bool over the joined keys
    (summaries first): row i of window ``w = i // window`` keeps the
    summaries ``m < w * per_window`` and the token keys
    ``w * window <= j <= i``."""
    rows = jnp.arange(l)[:, None]
    w = rows // window
    summaries = jnp.arange(l // window * per_window)[None] < w * per_window
    cols = jnp.arange(l)[None]
    return jnp.concatenate(
        [summaries, (cols <= rows) & (cols >= w * window)], axis=1)


def _einsum_eva_attention(q, k, v, k_summary, v_summary, window: int,
                          scale: float):
    """The joint softmax with its [L, L / chunk + L] scores held:
    float32 scores, probabilities back in the values' dtype."""
    l = q.shape[2]
    keep = _eva_keep(l, window, k_summary.shape[2] // (l // window))
    keys = jnp.concatenate([k_summary, k], axis=2)
    values = jnp.concatenate([v_summary, v], axis=2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, keys,
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(keep[None, None], logits, NEG_INF), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(values.dtype), values)


def eva_attention_path(platform: str, l: int, window: int, chunk: int,
                       head_dim: int, heads: int) -> str:
    """``flash`` or ``einsum`` for an EVA call: the owned kernels where
    ``attention_path`` gives them to a causal call of one window, the
    sequence is whole windows and a window's summaries fill 128-row
    blocks; else the path that holds the scores."""
    whole = l % window == 0 and window % chunk == 0
    if (whole and (window // chunk) % 128 == 0 and attention_path(
            platform, window, window, head_dim, heads, heads,
            causal=True) == "flash"):
        return "flash"
    return "einsum"


def eva_attention(q, k, v, k_summary, v_summary, window: int,
                  scale: Optional[float] = None):
    """EVA attention (Zheng et al., ICLR 2023, as EvaByte runs it) on
    q, k, v [B, H, L, D] and one summary a chunk of keys and of values,
    k_summary, v_summary [B, H, L / chunk, D]: row i of window
    ``w = i // window`` reads the token keys ``w * window <= j <= i``
    exactly and the summaries of the chunks of every earlier window,
    all under one softmax. Returns [B, H, L, D]; every op is named
    ``attention_<path>_eva``."""
    l, d = q.shape[2], q.shape[-1]
    n_sum = k_summary.shape[2]
    if l % window or n_sum == 0 or l % n_sum:
        raise ValueError(f"length {l} is not whole windows of {window} "
                         f"with {n_sum} summaries")
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    path = eva_attention_path(_platform(q), l, window, l // n_sum, d,
                              q.shape[1])
    with jax.named_scope(f"attention_{path}_eva"):
        if path == "flash":
            from analytics_zoo_tpu.ops.pallas_attention import (
                pallas_eva_attention)

            return pallas_eva_attention(q, k, v, k_summary, v_summary,
                                        window, scale)
        return _einsum_eva_attention(q, k, v, k_summary, v_summary,
                                     window, scale)
