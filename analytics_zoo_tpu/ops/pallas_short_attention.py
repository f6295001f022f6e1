"""Pallas TPU attention for short rows (L <= 512, heads of 64): forward
and one backward kernel over operands laid out as the projection wrote
them, ``[B, L, H x 64]``.

Where the blockwise kernels of ``pallas_attention`` walk
(batch x heads, q-blocks, kv-blocks) over heads-first operands, one
head of a BERT-shaped call ([384, 64]) is a single block: 38 MFLOP a
grid step, nothing to pipeline, tiles that fill 64 of 128 lanes and
four transposes a layer a direction to make them. Here:

- **the whole row is one tile**: a head's [L, L] float32 scores fit
  VMEM (1 MB at 512), so there is no kv grid dimension, no running
  max / sum / rescale; a plain softmax over the tile;
- **several heads a grid step**: the grid is (batch, head groups); a
  group is ``_heads_a_step`` heads = whole 128-lane tiles of the
  ``H x 64`` axis, walked in a static loop;
- **two heads share 128 lanes and are told apart by lane masks, not
  slices**: ``s_h = (q * lanes_h) k^T`` contracts all 128 lanes with
  the other head's zeroed, ``p_h (v * lanes_h)`` lands in head h's
  lanes alone, so the two heads' results add up to the tile. A 64-deep
  contraction and a 64-wide result each take a full 128 x 128 MXU pass
  anyway: the masks cost no pass and save every lane shuffle;
- **the backward regenerates the probabilities from the tile itself**
  (one tile holds the whole row, so max and sum are recomputed
  exactly): saved for it are q, k, v and the output; no logsumexp, no
  probabilities; ``delta = rowsum(do * o)`` is computed inside.

The arithmetic is ``attention._einsum_attention``'s: operands in their
own dtype (bf16 on the chip), float32 scores, statistics and
accumulators, probabilities rounded to the values' dtype before
``P V`` (here before the normalisation, as the blockwise kernel does).

A ``pallas_call`` is opaque to the partitioner, which would gather its
operands onto every chip: under a mesh ``attention.packed_attention``
runs these kernels inside a ``shard_map``, batch and head pairs
sharded, ``L`` and the 128 lanes of a pair whole, no collective
(docs/kernels.md "Short rows").
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops import pallas_attention

HEAD_DIM = 64       # the one head width the kernels serve
MAX_SEQ = 512       # a head's [L, L] float32 tile: 1 MB
_LANES = 128


# rows of [L, .] scores a grid step may walk (heads x L): on the v5e 12
# heads a step beat 6 / 4 / 2 by 12-14 % at L 128 and 256 and came
# within 2 % of the best at 384 and 512 (docs/kernels.md "Short rows")
_STEP_ROWS = 12 * 512


def _heads_a_step(l: int, heads: int) -> int:
    """Heads a grid step: the most whole lane tiles (pairs of heads)
    that divide ``heads`` and keep a step's [L, L] work under
    ``_STEP_ROWS`` rows of scores, so that a step amortises its fixed
    cost and the unrolled loop stays short."""
    pairs = heads // 2
    best = 1
    for n in range(1, pairs + 1):
        if pairs % n == 0 and 2 * n * l <= _STEP_ROWS:
            best = n
    return 2 * best


def _lanes_of(head: int):
    """[1, 128] bool: the lanes of the ``head``-th head of a tile."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    return (lane >= head * HEAD_DIM) & (lane < (head + 1) * HEAD_DIM)


def _only(lanes, x):
    return jnp.where(lanes, x, jnp.zeros_like(x))


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a b^T
_NN = ((1,), (0,))      # a b
_TN = ((0,), (0,))      # a^T b


def _short_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float):
    for tile in range(q_ref.shape[-1] // _LANES):
        cols = pl.ds(tile * _LANES, _LANES)
        q, k, v = q_ref[0, :, cols], k_ref[0, :, cols], v_ref[0, :, cols]
        out = None
        for head in range(_LANES // HEAD_DIM):
            lanes = _lanes_of(head)
            s = _dot(_only(lanes, q), k, _NT) * scale       # [L, L] f32
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            total = jnp.sum(e, axis=-1, keepdims=True)      # [L, 1]
            pv = _dot(e.astype(v.dtype), _only(lanes, v), _NN) * (1.0 / total)
            out = pv if out is None else out + pv           # [L, 128]
        o_ref[0, :, cols] = out.astype(o_ref.dtype)


def _short_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, dq_ref, dk_ref,
                      dv_ref, *, scale: float):
    """Per head: s = q_h k^T and its softmax p regenerated from the
    tile, dp = do_h v^T, ds = p (dp - delta) with
    delta = rowsum(do_h * o_h), then dv = p^T do_h, dk = ds^T q_h and
    dq = ds k_h: five MXU products, each landing in head h's lanes."""
    for tile in range(q_ref.shape[-1] // _LANES):
        cols = pl.ds(tile * _LANES, _LANES)
        q, k, v = q_ref[0, :, cols], k_ref[0, :, cols], v_ref[0, :, cols]
        do = do_ref[0, :, cols]
        # the product of two bf16 values is exact in float32
        do_o = do.astype(jnp.float32) * o_ref[0, :, cols].astype(jnp.float32)
        dq = dk = dv = None
        for head in range(_LANES // HEAD_DIM):
            lanes = _lanes_of(head)
            q_h, do_h = _only(lanes, q), _only(lanes, do)
            s = _dot(q_h, k, _NT) * scale                   # [L, L] f32
            e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            p = e * (1.0 / jnp.sum(e, axis=-1, keepdims=True))
            dp = _dot(do_h, v, _NT)                         # [L, L]
            delta = jnp.sum(_only(lanes, do_o), axis=-1, keepdims=True)
            ds = (p * (dp - delta)).astype(q.dtype)
            dv_h = _dot(p.astype(do.dtype), do_h, _TN)      # [L, 128]
            dk_h = _dot(ds, q_h, _TN)
            dq_h = _dot(ds, _only(lanes, k), _NN)
            dq = dq_h if dq is None else dq + dq_h
            dk = dk_h if dk is None else dk + dk_h
            dv = dv_h if dv is None else dv + dv_h
        dq_ref[0, :, cols] = (dq * scale).astype(dq_ref.dtype)
        dk_ref[0, :, cols] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, :, cols] = dv.astype(dv_ref.dtype)


def _vmem_bytes(l: int, width: int, itemsize: int, operands: int) -> int:
    """What a call may ask of VMEM, from above: its ``operands``
    [L, width] blocks (two buffers each) and [L, L] float32 tiles, five
    for a head's intermediates and one more a head of the step (Mosaic
    gives the unrolled heads' temporaries no common buffer: compiled for
    a described v5e, 12 heads at 512 hold 10.5 MiB beside their blocks
    in the forward, 4.8 in the backward)."""
    return (2 * operands * l * width * itemsize
            + (5 + width // HEAD_DIM) * 4 * l * l)


@functools.partial(jax.jit, static_argnums=(0, 2, 3, 4, 5), inline=True)
def _call(kernel, operands, n_out: int, scale: float, heads: int,
          interpret: bool):
    """One kernel over (batch, head groups) with every operand and
    result a [1, L, group width] block of its [B, L, H x 64] array.
    Jitted and inlined: the layers of a model make the same call, and
    the kernel's body (a dozen heads unrolled) is traced for the first
    of them alone, not a third of a second a layer a direction."""
    b, l, width = operands[0].shape
    group = _heads_a_step(l, heads) * HEAD_DIM
    spec = pl.BlockSpec((1, l, group), lambda i, g: (i, 0, g))
    shape = jax.ShapeDtypeStruct((b, l, width), operands[0].dtype)
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=pallas_attention._VMEM_SLACK + _vmem_bytes(
            l, group, shape.dtype.itemsize, len(operands) + n_out))
    return pl.pallas_call(
        functools.partial(kernel, scale=scale),
        grid=(b, width // group),
        in_specs=[spec] * len(operands),
        out_specs=spec if n_out == 1 else [spec] * n_out,
        out_shape=shape if n_out == 1 else [shape] * n_out,
        compiler_params=params,
        interpret=interpret,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def pallas_short_attention(q, k, v, heads: int,
                           scale: Optional[float] = None):
    """Softmax attention without a mask on q, k, v ``[B, L, heads x 64]``
    (each head's 64 columns side by side, as a fused projection writes
    them); out in the same layout. ``L`` a multiple of 128 up to
    ``MAX_SEQ``, an even number of heads (two share a lane tile)."""
    return _forward(q, k, v, heads, scale)


def _forward(q, k, v, heads: int, scale: Optional[float]):
    return _call(_short_fwd_kernel, (q, k, v), 1,
                 _checked_scale(q, k, v, heads, scale), heads,
                 pallas_attention._interpret())


def _checked_scale(q, k, v, heads: int, scale: Optional[float]) -> float:
    _, l, width = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape} must agree")
    if width != heads * HEAD_DIM or heads % 2:
        raise ValueError(f"{heads} heads over {width} columns: the short-"
                         f"row kernel takes an even number of heads of "
                         f"{HEAD_DIM}")
    if l % _LANES or l > MAX_SEQ:
        raise ValueError(f"length {l} is not a multiple of {_LANES} up to "
                         f"{MAX_SEQ}")
    return float(scale) if scale is not None else 1.0 / np.sqrt(HEAD_DIM)


def _vjp_fwd(q, k, v, heads, scale):
    out = _forward(q, k, v, heads, scale)
    return out, (q, k, v, out)


def _vjp_bwd(heads, scale, res, g):
    q, k, v, out = res
    return tuple(_call(_short_bwd_kernel, (q, k, v, out, g), 3,
                       _checked_scale(q, k, v, heads, scale), heads,
                       pallas_attention._interpret()))


pallas_short_attention.defvjp(_vjp_fwd, _vjp_bwd)
